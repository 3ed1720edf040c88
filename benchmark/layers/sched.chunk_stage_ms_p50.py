"""Host time to stage one prefill chunk, the enqueue call left out: the
``sched_ms`` of the window's ``prefill_chunk`` flight rows
(``Scheduler._step_prefill_chunk``: the chunk's wall less
``adm.last_launch_ms``), median. What the device's idle gaps under
``sched.prefill_chunk`` are made of. None in a window with no chunk row."""

from harness import hostclock
from harness import metrics as mtr


def read(ctx):
    return mtr.percentile([r["sched_ms"] for r in hostclock.window_rows(
        ctx, ("prefill_chunk",))], 50)
