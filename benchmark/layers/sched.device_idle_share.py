"""The device idle time the scheduler owns: idle time of the traced slice
that falls under one of the engine thread's ``sched.*`` annotations other
than ``sched.wait_device`` and ``sched.idle`` (in those it waits), over the
slice, mean over chips. ``device.idle_share`` minus this is idle nobody has
named. None where the program annotates nothing."""


def read(ctx):
    tr = ctx.get("trace") or {}
    if tr.get("idle_owned_s") is None:
        return None
    return 100.0 * tr["idle_owned_s"] / tr["window_s"]
