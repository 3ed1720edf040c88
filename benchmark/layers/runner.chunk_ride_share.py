"""Of the window's admissions whose last chunk took a program of at most
``RIDE_ROWS`` rows, the share whose chunk RODE the decode step (PR 59: ONE
launch for both, the weights read once), from the flight ring's rows, compile
rows left out: ``decode_chunk`` rows over ``decode_chunk`` rows plus the
``prefill_chunk`` rows of such a bucket. With the engine's ``prefill_chunk`` of
512 tokens a chunk of a bucket that small IS a prompt's last (every other
chunk holds 512). What is left to 100 met an idle engine, a constrained
stream, or a dispatch of more than one step. None where the ring holds no
``decode_chunk`` row: the parent's programs have no such launch, and a cell
that steps aside (a family's own forward, a mesh) has none to count."""

from harness import layerlib as ll

RIDE_ROWS = 128         # localai_tpu/engine/runner.py RIDE_ROWS


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close,
                     ("decode_chunk", "prefill_chunk"))
    rides = sum(r["program"] == "decode_chunk" for r in rows)
    if not rides:
        return None
    small = sum(r["program"] == "prefill_chunk"
                and 0 < r.get("chunk_bucket", 0) <= RIDE_ROWS for r in rows)
    return 100.0 * rides / (rides + small)
