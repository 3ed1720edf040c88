"""Share of the decode programs' device time that the gated short convolution
takes: their operations staged under ``sconv/`` (models/lfm2.py: in_proj, the
slot's rows read, the convolution, the rows written back, out_proj) over ALL
of the decode programs' operations in the traced slice: how much of a step
the mixer that is a convolution and nothing else costs beside the experts.
``ssm.mixer_share``'s reduction over another scope. None where the program
names no such scope (every other configuration, and the parent)."""

import re

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)sconv/")


def read(ctx):
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    decode = [(scope, sec) for program, scope, _, sec in rows
              if re.search(PROGRAMS, program)]
    mixer = sum(sec for scope, sec in decode if SCOPE.search(scope))
    if not mixer:
        return None
    return 100.0 * mixer / sum(sec for _, sec in decode)
