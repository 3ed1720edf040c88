"""Decode's share of the HBM roofline, from the program's own counts (PR 39):
the bytes the decode executions of the traced slice had to move (the weights a
step reads, once a step, plus the K/V of every attended token: harness/work.py
over the configuration's family) over those executions' device time, against
the chip's peak bandwidth. Steps, query tokens a step (``live_slots``) and
attended tokens (``attended_tokens``, counted when the program was enqueued)
come from each execution's OWN ring row, found by the launch number
(harness/launches.py): nothing from the clients' timelines, no clock
arithmetic. It should agree with ``model.decode_bw_share``, whose counts are
the clients'. None where the slice holds no matched decode execution, or the
program counts none (the parent's)."""

from harness import launches
from harness import layerlib as ll
from harness import work

ROWS = ("decode", "decode_n")


def read(ctx):
    got = launches.matched(ctx, ROWS)
    seconds = sum(sec for _, sec in got)
    if not seconds or not sum(row["steps"] for row, _ in got):
        return None
    cell = ctx["cell"]
    need = {"bytes": work.decode_bytes(
        cell.family, cell.published, cell.config["engine"],
        [(row["steps"], row["steps"] * row["live_slots"]) for row, _ in got],
        sum(row["attended_tokens"] for row, _ in got))}
    return ll.share_of_roofline(need, seconds, ctx)
