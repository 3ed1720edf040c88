"""What the HTTP layer adds to the first token: the client's time from send
to first content chunk, minus the request's ``queued`` and ``prefill`` spans
(obs/trace.py). Median over the scored requests."""

from harness import layerlib as ll
from harness import metrics as mtr


def read(ctx):
    by_id = ll.spans(ctx)
    over = []
    for r in ll.scored(ctx):
        sp = by_id.get(r.trace_id)
        if (r.first is None or r.sent is None or not sp
                or "queued" not in sp or "prefill" not in sp):
            continue
        over.append(r.first - r.sent - sp["queued"][1] - sp["prefill"][1])
    return 1e3 * mtr.percentile(over, 50) if over else None
