"""The routed experts' matmuls against the HBM roofline: the bytes of the
experts that the traced slice's decode launches TOUCHED (the flight ring's
``experts_touched``, counted on the device: held experts with at least one
token, summed over the expert blocks and steps of a launch; each is an
expert's three matrices read once: the family's ``expert_bytes``) over the
device time of the decode programs' operations staged under ``moe/experts``
(models/qwen3_next.py: the loop over the experts that have a token), against
the chip's peak bandwidth. Bytes-bound at a decode step's few tokens an
expert. Rows are the slice's by their drain, as ``model.loop_pass_ms``'s.
None where the program names no such scope, its ring has no such column, or
the family prices no expert (every other configuration)."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)moe/experts(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family, "expert_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    touched = sum(r.get("experts_touched") or 0 for r in ll.flight(
        ctx, *win, ("decode", "decode_n")))
    if not seconds or not touched:
        return None
    need = {"bytes": cell.family.expert_bytes(
        cell.published, touched,
        work.WEIGHT_BYTES[cell.config["engine"].get("quantization")])}
    return ll.share_of_roofline(need, seconds, ctx)
