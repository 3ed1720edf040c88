"""1 - the union of the device's operation intervals over the traced slice,
mean over chips (the worst chip goes on the ``trace`` line). The first number
a perf_opt issue reads. Taken with the profiler on (device and host planes;
the Python tracer is off unless asked for, and the benchmark never asks), so
if anything it overstates the untraced idle share."""


def read(ctx):
    tr = ctx.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]
