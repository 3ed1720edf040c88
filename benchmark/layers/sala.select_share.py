"""Share of the traced slice's device time that the decode steps' SELECTION
takes: the operations the decode programs staged under ``sparse/compress``
(the step's key added to the running half-window sums, a compressed key laid
into the slot's rows when a window closes), ``sparse/score`` (every query
head against every compressed key of its K/V head, a softmax a head, the
group's sum, the best window a block) and ``sparse/select`` (the top-k and
its sort), models/minicpm_sala.py, over the device's busy time in the slice.
None of it reads a row of the pool: all of it is the price of choosing which
blocks to read, so lower is better; what it buys is ``sala.
sparse_attend_roofline``'s eighth of the K/V. None where the program names no
such scope (every other configuration, and the parent)."""

import re

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)sparse/(compress|score|select)(/|$)")


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
