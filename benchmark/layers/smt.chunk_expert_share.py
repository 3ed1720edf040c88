"""Share of the traced slice's device time that ADMISSIONS spend in the
routed experts: the prefill programs' operations staged under
``layers/moe/experts`` over the device's busy time in the slice
(``mla.chunk_attend_share``'s reduction over another scope).
A family whose last chunk cannot ride the decode step (a stack with more than
one kind of attention layer: ``PagedLayout.ride`` steps aside) reads a
layer's experts a second time for each admission's chunk: all 64 of each of
12 layers here, a whole step's bytes. What a ride for such a stack (ROADMAP
A20 (2)) would take off the path. None where no prefill program ran in the
slice or the program names no such scope (the parent)."""

import re

PROGRAMS = r"prefill"       # jit__prefill_paged_fn
SCOPE = re.compile(r"(^|/)layers/moe/experts(/|$)")


def read(ctx):
    trace = ctx.get("trace") or {}
    rows = trace.get("op_rows") or ()
    busy = trace.get("busy_s")
    chunks = sum(sec for program, scope, _, sec in rows
                 if re.search(PROGRAMS, program) and SCOPE.search(scope))
    if not chunks or not busy:
        return None
    return 100.0 * chunks / busy
