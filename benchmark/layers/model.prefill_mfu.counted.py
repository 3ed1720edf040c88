"""Prefill's model FLOP/s utilisation, from the program's own counts (PR 39):
the flops of the REAL tokens of the prefill executions in the traced slice
(harness/work.py; ``chunk_tokens`` of each execution's ring row, each attending
the ``chunk_offset`` cached tokens in front of its chunk and causally its own)
over those executions' device time, against the chip's peak bf16 rate. An
execution is tied to its row by the launch number (harness/launches.py), so
what is summed above is the work of exactly the executions timed below.
Padding is not work: the number rises when a chunk stops computing it. None
where the slice holds no matched prefill execution, or the program counts
none (the parent's)."""

from harness import launches, work

ROWS = ("prefill_chunk",)


def read(ctx):
    got = launches.matched(ctx, ROWS)
    seconds = sum(sec for _, sec in got)
    tokens = sum(row["chunk_tokens"] for row, _ in got)
    if not seconds or not tokens:
        return None
    cell = ctx["cell"]
    flops = work.prefill_flops(
        cell.family, cell.published, tokens,
        sum(launches.real_pairs(row) for row, _ in got))
    return (100.0 * flops / seconds
            / (ctx["peak"]["bf16_flops"] * cell.chips))
