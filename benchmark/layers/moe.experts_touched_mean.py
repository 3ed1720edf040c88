"""Held experts a decode step touched in one expert block, over the window's
decode dispatches (flight ring): the sum of their ``experts_touched`` (counted
on the device: held experts with at least one token, summed over the blocks
and steps of a launch) over steps x expert blocks (one a layer). Each is an
expert's three matrices read, so it is what the routed half of a step's bytes
goes with; the family's ``experts_touched(hf, tokens)`` is what uniform
routing would give (30.0 of 64 at 32 tokens, top-10 of 512). None where the
ring has no such column (a model without routed experts, the parent's)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = [r for r in ll.flight(ctx, w.t_open, w.t_close,
                                 ("decode", "decode_n"))
            if r.get("experts_touched")]
    steps = sum(r["steps"] for r in rows)
    if not steps:
        return None
    blocks = int(ctx["cell"].published["num_hidden_layers"])
    return sum(r["experts_touched"] for r in rows) / (steps * blocks)
