"""The full layers' attend over the CHOSEN rows against its roofline: the
bytes of the latent rows the traced slice's decode launches selected (the
flight ring's ``selected_tokens``: each stream's context cut to
``index_topk``, summed over steps and streams, counted when a launch was
enqueued; the family's ``select_bytes``: 576 elements a row a full layer,
whatever lanes a pool pads them to), q read and the output written, and the
flops of the same pairs in the PUBLISHED form (``select_flops``: the smaller
of the two forms' counts), over the device time of the decode programs'
operations staged under ``attn.sparse_decode`` (engine/kvcache.py
``latent_sparse_decode``: the chosen rows gathered through the tables, the
absorbed attend over them), against the chip's peaks. A step that read the
latent rows of the WHOLE context there would take the time of 16x the rows
and read under a tenth of this. None where the program names no such scope
(every other configuration, and the parent), the ring has no such column or
the family prices no selection."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)attn\.sparse_decode(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family, "select_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    held = ll.flight(ctx, *win, ("decode", "decode_n"))
    selected = sum(r.get("selected_tokens") or 0 for r in held)
    tokens = sum((r.get("live_slots") or 0) * r["steps"] for r in held)
    if not seconds or not selected:
        return None
    need = {"bytes": cell.family.select_bytes(
        cell.published, selected, tokens,
        work.KV_BYTES[cell.config["engine"].get("kv_dtype", "bfloat16")]),
        "flops": cell.family.select_flops(cell.published, selected)}
    return ll.share_of_roofline(need, seconds, ctx)
