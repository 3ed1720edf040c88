"""How late the generator sent: actual send minus due, 99th percentile over
the scored requests, on the generator's own clock. Validity of every cell: a
starved generator is not a fast server."""


def read(ctx):
    return ctx["send_late_ms_p99"]
