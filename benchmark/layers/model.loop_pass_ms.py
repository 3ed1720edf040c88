"""Device time of ONE pass over the layer stack in a looped decoder's decode
step: the decode programs' operations that the program staged under
``loop.pass`` (models/llama.py: the layer scan of one pass, with its
projections, kernel calls and pool writes; the norm between passes is
``loop.norm`` and not in it), summed over the traced slice, over the passes
the slice's decode dispatches ran (the flight ring's ``passes``). What a
later change that skips a pass, or makes one cheaper, would move. None where
the program names no such scope or its ring has no such column (a model that
runs its stack once; a program from before PR 37)."""

import re

from harness import layerlib as ll

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)loop\.pass(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    if win is None or not rows:
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    passes = sum(r.get("passes") or 0 for r in ll.flight(
        ctx, *win, ("decode", "decode_n")))
    if not seconds or not passes:
        return None
    return 1e3 * seconds / passes
