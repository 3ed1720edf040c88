"""Host time the engine thread spends on one dispatch's tokens (stop checks,
detokenising, handing chunks to the streams): the ``sched.process``
annotations (engine/scheduler.py ``_process_rows``) inside the traced slice,
90th percentile. None where the program annotates nothing."""

from harness import metrics as mtr


def read(ctx):
    tr = ctx.get("trace") or {}
    lo, hi = tr.get("window_at_s", (0.0, 0.0))
    took = [e - s for s, e, name in tr.get("phases") or ()
            if name == "sched.process" and lo <= s < hi]
    return 1e3 * mtr.percentile(took, 90) if took else None
