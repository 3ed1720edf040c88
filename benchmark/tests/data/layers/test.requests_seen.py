"""A per-layer metric a later PR might add: a new file, found by name."""


def read(ctx):
    return float(len(ctx["records"]))
