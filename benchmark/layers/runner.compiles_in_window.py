"""New program shapes first dispatched inside the window
(``localai_xla_compile_total`` at its close minus at its opening). Expected 0;
anything else voids the run's tails."""


def read(ctx):
    return ctx["compiles_in_window"]
