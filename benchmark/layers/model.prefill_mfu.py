"""Prefill's model FLOP/s utilisation: the flops of the prompt tokens
prefilled inside the traced slice (harness/work.py; tokens from the requests'
``prefill`` spans) over the device time of the prefill programs in the trace,
against the chip's peak bf16 rate."""

from harness import layerlib as ll
from harness import trace_reduce, work

PROGRAMS = r"prefill"       # jit__prefill_paged_fn


def read(ctx):
    win = ll.trace_window(ctx)
    if win is None:
        return None
    seconds, _ = trace_reduce.module_seconds(ctx["trace"], PROGRAMS)
    tokens, pairs = ll.prefilled_in(ctx, *win)
    if not seconds or not tokens:
        return None
    cell = ctx["cell"]
    flops = work.prefill_flops(cell.family, cell.published, tokens, pairs)
    return (100.0 * flops / seconds
            / (ctx["peak"]["bf16_flops"] * cell.chips))
