"""The WINDOW layers' latent decode attention against its roofline: the bytes
of the latent rows those layers' calls must read (the family's
``window_bytes`` of the traced slice's ``window_tokens``: the flight ring's
count, taken when a decode launch was enqueued, of every live stream's
context cut to the attention window, summed over the launch's steps; 1088
elements a row a window layer) and the flops of the same pairs in the
published form (``window_flops``), over the device time of the decode
programs' operations staged under ``attn.latent_window`` (engine/kvcache.py
``latent_window_decode``: each stream's window's blocks gathered through the
tables, the absorbed attend over them), against the chip's peaks. A call that
walked a stream's whole context would take the time of all of it and read 65x
lower here. Rows are the slice's by their drain, as
``moe.expert_bw_share``'s. None where the program names no such scope, its
ring has no such column, or the family prices no window (every other
configuration, and the parent)."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)attn\.latent_window(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family, "window_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    tokens = sum(r.get("window_tokens") or 0 for r in ll.flight(
        ctx, *win, ("decode", "decode_n")))
    if not seconds or not tokens:
        return None
    need = {"bytes": cell.family.window_bytes(
        cell.published, tokens,
        work.KV_BYTES[cell.config["engine"].get("kv_dtype", "bfloat16")]),
        "flops": cell.family.window_flops(cell.published, tokens)}
    return ll.share_of_roofline(need, seconds, ctx)
