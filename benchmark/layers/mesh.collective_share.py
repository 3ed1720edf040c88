"""Share of the device's busy time spent in collectives: the union of the
intervals of the operations whose HLO name is a collective's (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``, ``all-to-all``,
``collective-broadcast``, their ``-start``/``-done`` halves included:
harness/trace_reduce.py COLLECTIVE) over the union of all operations', each
per chip, mean over chips, in the traced slice. Not a share of a peak: what a
tensor-parallel step pays for being spread over chips. A chip's ``XLA Ops``
line is serial, so this is the time the core spent IN a collective, nothing
else running: exposed time. What a ``-start`` leaves to run under other
operations until its ``-done`` is on no line, so how much communication is
hidden cannot be read from a trace (PERF.md section 7). 0 on a mesh whose
trace holds no collective (renamed or gone: look); None on one chip, where
none runs, and without a trace. The program names no ``mesh.*`` scope yet:
the HLO names are all this rests on."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["chips"] < 2:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
