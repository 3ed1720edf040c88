"""Share of the requests attempted that met the cell's TTFT and TPOT limits
(a failed request misses). Moves nothing: it tells a later benchmark PR when
an optimisation has overtaken the cell's rate and the knee must be found
again."""

from harness import metrics as mtr


def read(ctx):
    share = mtr.attained(ctx["records"], ctx["window"], ctx["loop"],
                         ctx["cell"].drive["limits"])
    return None if share is None else 100.0 * share
