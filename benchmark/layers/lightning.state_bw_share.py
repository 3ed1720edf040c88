"""The Lightning layers' recurrent state against the HBM roofline: the bytes
a decode step must move for its live slots (every Lightning layer's float32
state S [heads, 128, 128] read and written once: the family's ``state_bytes``
over the slice's (live slot, step) pairs, from the flight ring's
``live_slots`` and ``steps``) over the device time of the decode programs'
operations staged under ``lightning/state`` (models/minicpm_sala.py: the
step's kernel on the carried array, or the per-slot rows read, the XLA step
and the rows written back), against the chip's peak bandwidth: the step
kernel's share of its roofline, the twin of ``ssm.state_bw_share`` and
``gdn.state_bw_share`` (the same kernel, ops/gdn.py without the delta
correction, under another family's scope). A need, not what was fused: a
recurrence that passes over S more than twice reads low. None where the
program names no such scope or the family prices no state (every other
configuration, and the parent)."""

import re

from harness import layerlib as ll

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)lightning/state(/|$)")


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
