"""Of the window's admissions of a model with recurrent state, the share whose
shared prefix's state was RESTORED from a snapshot in front of the tail's
first chunk (engine/paged.py: a snapshot a registered prompt, kept with its
chain's last block), from the flight ring's prefill rows, compile rows left
out: ``chunk_state`` is what a chunk went on from (1: zero state, 2: the
slot's own, 3: a snapshot laid into the slot's rows in front of it), and an
admission's FIRST chunk reads 1 or 3, so the share is rows of 3 over rows of
1 and 3. Below 100 an admission prefilled its whole prompt, the shared
document included. None where the ring has no such column or no row reads 3
(a model that carries no state, a recurrent one whose prompts share nothing,
and the parent)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close,
                     ("prefill_chunk", "decode_chunk"))
    restored = sum(r.get("chunk_state") == 3 for r in rows)
    cold = sum(r.get("chunk_state") == 1 for r in rows)
    if not restored:
        return None
    return 100.0 * restored / (restored + cold)
