"""The paged decode attention kernel (ops/attention.py) against its roofline:
the least time for the K/V bytes and the flops of the tokens attended inside
the traced slice (bytes-bound at these shapes; harness/work.py) over the
kernel's device time, found by the name the trace shows today."""

from harness import layerlib as ll
from harness import trace_reduce, work

# the program names no kernel yet: in the decode programs the one Pallas
# custom call is the paged attention kernel (a prefill kernel, when one
# serves, runs in the prefill programs: see prefill_attn_roofline)
KERNEL = r"custom-call:tpu_custom_call"


def read(ctx):
    win = ll.trace_window(ctx)
    if win is None:
        return None
    seconds, _ = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    tokens, attended = ll.attended_in(ctx, *win)
    if not seconds or not tokens:
        return None
    cell = ctx["cell"]
    need = work.paged_decode_attn(cell.family, cell.published,
                                  cell.config["engine"], attended, tokens)
    return ll.share_of_roofline(need, seconds, ctx)
