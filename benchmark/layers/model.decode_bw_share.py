"""Decode's share of the HBM roofline: the bytes the decode steps of the
traced slice had to move (the weights a step reads, once a step, plus the K/V
of every attended token: harness/work.py over the configuration's family)
over the device time of the decode programs in the trace, against the chip's
peak bandwidth. Steps and the tokens they made come from the flight ring,
attended tokens from what the clients received, both inside the slice."""

from harness import layerlib as ll
from harness import trace_reduce, work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn


def read(ctx):
    win = ll.trace_window(ctx)
    if win is None:
        return None
    seconds, _ = trace_reduce.module_seconds(ctx["trace"], PROGRAMS)
    dispatches = [(r["steps"], r["tokens"]) for r in ll.flight(
        ctx, *win, ("decode", "decode_n"))]
    if not seconds or not sum(steps for steps, _ in dispatches):
        return None
    _, attended = ll.attended_in(ctx, *win)
    cell = ctx["cell"]
    need = {"bytes": work.decode_bytes(
        cell.family, cell.published, cell.config["engine"], dispatches,
        attended)}
    return ll.share_of_roofline(need, seconds, ctx)
