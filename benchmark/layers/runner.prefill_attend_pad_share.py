"""The share of the (query, attended) pairs prefill's attend computes that no
real token needs, over the window's prefill launches (flight ring, PR 39):
1 - sum of real pairs (each real token of a chunk attends the cached tokens in
front of it and causally its own: harness/launches.py) over sum of
``chunk_bucket`` x ``chunk_ctx`` (every row of the bucket against every
position the attend spans), compile rows left out. What an attend cut to the
prefix a chunk holds would move. None where the window holds no chunk, or the
ring counts none (the parent's)."""

from harness import launches


def read(ctx):
    rows = launches.window_chunks(ctx)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(launches.real_pairs(r) for r in rows)
                    / sum(r["chunk_bucket"] * r["chunk_ctx"] for r in rows))
