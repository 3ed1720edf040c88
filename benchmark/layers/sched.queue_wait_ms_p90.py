"""Submit to admission: each scored request's ``queued`` span, 90th
percentile."""

from harness import layerlib as ll
from harness import metrics as mtr


def read(ctx):
    by_id = ll.spans(ctx)
    waits = [by_id[r.trace_id]["queued"][1] for r in ll.scored(ctx)
             if "queued" in by_id.get(r.trace_id, {})]
    return 1e3 * mtr.percentile(waits, 90) if waits else None
