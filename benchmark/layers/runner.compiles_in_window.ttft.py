"""``runner.compiles_in_window`` in a cell that does not report
``stall_ms_p98``: a program compiled inside the window holds every request
behind it, first tokens too. Expected 0."""


def read(ctx):
    return ctx["compiles_in_window"]
