"""The sparse layers' attend over the SELECTED blocks against its roofline:
the K/V bytes of the blocks the traced slice's decode rows selected (the
flight ring's ``sparse_rows``: of a launch's (live slot, step) pairs those at
or past ``dense_len``, counted when the launch was enqueued; the family's
``selected_kv_bytes``: ``topk`` blocks of ``block_size`` tokens a K/V head a
sparse layer, q read and the output written beside them), over the device
time of the decode programs' operations staged under ``sparse/attend``
(models/minicpm_sala.py around engine/kvcache.py ``select_decode``: the
compacted tables built, the paged decode kernel over them with rows =
(stream, K/V head)), against the chip's peak bandwidth. A step that read the
K/V of the WHOLE context there would take the time of 8x the blocks at the
cell's contexts and read about an eighth of this. Only the slice's sparse
rows are priced: a slice with dense rows among them reads low, never high.
None where the program names no such scope (every other configuration, and
the parent), the ring has no such column or the family prices no selection."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)sparse/attend(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family,
                                              "selected_kv_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    sparse = sum(r.get("sparse_rows") or 0
                 for r in ll.flight(ctx, *win, ("decode", "decode_n")))
    if not seconds or not sparse:
        return None
    need = {"bytes": cell.family.selected_kv_bytes(
        cell.published, sparse,
        work.KV_BYTES[cell.config["engine"].get("kv_dtype", "bfloat16")])}
    return ll.share_of_roofline(need, seconds, ctx)
