"""Share of the traced slice's device time that the decode steps' SELECTION
takes: the operations the decode programs staged under ``attn.select``
(engine/kvcache.py ``select_rows``: the exact ``index_topk``-th largest score
of every stream by a threshold found a bit at a time, the ties' ranks, the
chosen positions compacted by rank), over the device's busy time in the
slice. The selection reads and writes no row of the pool: all of it is the
price of choosing, so lower is better; a sort-based top-k of 2048 from 34 k
reads several times this. None where the program names no such scope (every
other configuration, and the parent)."""

import re

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)attn\.select(/|$)")


def read(ctx):
    trace = ctx.get("trace") or {}
    rows = trace.get("op_rows") or ()
    busy = trace.get("busy_s")
    if not rows or not busy:
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    if not seconds:
        return None
    return 100.0 * seconds / busy
