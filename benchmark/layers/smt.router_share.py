"""Share of the decode programs' device time that the ROUTERS take: their
operations staged under ``moe/router`` (models/smallthinker.py: a [rows, D] x
[D, 64] product in front of attention, its top-k, the weights and the order
of the experts' walk) over ALL of the decode programs' operations in the
traced slice. Twelve small latency-bound routers a step, each in front of
its layer's q, k and v: what the router's place costs beside the experts it
feeds. ``sconv.mixer_share``'s reduction over another scope. None where the
program names no such scope (a model without routed experts, the parent)."""

import re

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)moe/router(/|$)")


def read(ctx):
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    decode = [(scope, sec) for program, scope, _, sec in rows
              if re.search(PROGRAMS, program)]
    routers = sum(sec for scope, sec in decode if SCOPE.search(scope))
    if not routers:
        return None
    return 100.0 * routers / sum(sec for _, sec in decode)
