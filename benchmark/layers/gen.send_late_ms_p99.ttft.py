"""``gen.send_late_ms_p99`` in an open-loop cell that does not report
``stall_ms_p98``: a request sent late is charged to TTFT, which counts from
the instant the schedule said to send."""


def read(ctx):
    return ctx["send_late_ms_p99"]
