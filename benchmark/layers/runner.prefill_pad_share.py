"""The share of the rows prefill computes that are padding, over the window's
prefill launches (flight ring, PR 39): 1 - sum of ``chunk_tokens`` (real) over
sum of ``chunk_bucket`` (the rows the bucket's program computes), compile rows
left out. What a finer bucket, or a chunk packed from two prompts, would
move. None where the window holds no chunk, or the ring counts none (the
parent's)."""

from harness import launches


def read(ctx):
    rows = launches.window_chunks(ctx)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(r["chunk_tokens"] for r in rows)
                    / sum(r["chunk_bucket"] for r in rows))
