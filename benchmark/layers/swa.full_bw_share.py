"""The FULL-attention layers' decode attention against the HBM roofline, in a
stack that has window layers too: the K/V bytes of every cached token the
traced slice's decode launches attended (the flight ring's
``attended_tokens``, counted when a launch was enqueued; the family's
``kv_bytes_per_token``, which for such a family counts its full layers alone)
and the flops of the same pairs, over the device time of the decode programs'
operations staged under ``attn.paged_decode`` (the paged kernel called with no
window), against the chip's peaks. Bytes-bound at these shapes. Beside
``swa.window_bw_share`` it says which kind of layer a long context costs. None
where the family prices no window (a stack of one kind:
``paged_decode_attn_roofline`` is its reader), where the ring has no
``window_tokens`` column, or where the program names no such scope."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"
SCOPE = re.compile(r"(^|/)attn\.paged_decode(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family, "window_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    held = [r for r in ll.flight(ctx, *win, ("decode", "decode_n"))
            if r.get("window_tokens")]
    attended = sum(r.get("attended_tokens") or 0 for r in held)
    if not seconds or not attended:
        return None
    need = {"bytes": attended * work.kv_bytes_per_token(
        cell.family, cell.published, cell.config["engine"]),
        "flops": cell.family.attn_flops(cell.published, attended)}
    return ll.share_of_roofline(need, seconds, ctx)
