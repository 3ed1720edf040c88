"""Share of the decode programs' device time that the state-space mixer
takes: their operations staged under ``ssm/`` (models/falcon_h1.py: in_proj,
the conv, the state's step, the gated norm, out_proj) over ALL of the decode
programs' operations in the traced slice: whether the mechanism does most of
a step's work. None where the program names no such scope (every other
configuration, and the parent)."""

import re

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)ssm/")


def read(ctx):
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    decode = [(scope, sec) for program, scope, _, sec in rows
              if re.search(PROGRAMS, program)]
    mixer = sum(sec for scope, sec in decode if SCOPE.search(scope))
    if not mixer:
        return None
    return 100.0 * mixer / sum(sec for _, sec in decode)
