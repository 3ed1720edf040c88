"""The Gated DeltaNet layers' recurrent state against the HBM roofline: the
bytes a decode step must move for its live slots (every DeltaNet layer's
float32 state S read and written once, its conv rows read and written: the
family's ``state_bytes`` over the slice's (live slot, step) pairs, from the
flight ring's ``live_slots`` and ``steps``) over the device time of the decode
programs' operations staged under ``gdn/state`` (models/qwen3_next.py: the
per-slot arrays read, the recurrence, the arrays written back), against the
chip's peak bandwidth. A need, not what was fused: a recurrence that passes
over S more than twice reads low. Rows are the slice's by their drain, as
``model.loop_pass_ms``'s. None where the program names no such scope or the
family prices no state (every other configuration)."""

import re

from harness import layerlib as ll

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)gdn/state(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family, "state_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    slot_steps = sum(r["steps"] * (r.get("live_slots") or 0)
                     for r in ll.flight(ctx, *win, ("decode", "decode_n")))
    if not seconds or not slot_steps:
        return None
    need = {"bytes": cell.family.state_bytes(cell.published, slot_steps)}
    return ll.share_of_roofline(need, seconds, ctx)
