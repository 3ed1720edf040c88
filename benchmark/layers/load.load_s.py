"""Child process start to the model listed in /readyz: backend init plus the
program's load (synthetic weights generated on the device)."""


def read(ctx):
    return ctx["setup"]["load_s"]
