"""Share of the traced slice's device time that the decode steps' SAMPLER
takes: the operations the decode programs staged under ``sample``
(engine/sampling.py: the penalties, the candidates' stages ``tile_max`` /
``tile_topk`` where the row block is wide, ``chunk_max``, ``topk``, under a
mesh ``merge``, and the draw over the 256 candidates), over the device's busy
time in the slice. It goes with slots x vocabulary and buys no token a better
letter, so lower is better. The ``sample`` scope is PR 45's: the parent of
the PR that added this reader reads too. None where the trace has no such
rows (a program that names no scope, or no trace)."""

import re

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)sample(/|$)")


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
