"""Held experts a decode step touched in one expert block, for the cell that
holds all 64 of a layer: ``moe.experts_touched_mean``'s reader as it stands
(the flight ring's ``experts_touched`` over steps x expert blocks, one a
layer), under a name of this cell's: that accepted entry lists its cells, and
appending one to it is a ``benchmark`` PR's (PERF.md section 7). 61.3 of 64
is what 32 rows choosing 6 of 64 independently would touch (the family's
``experts_touched``); fewer says the batch's tokens route alike."""

from pathlib import Path

from harness import spec

read = spec.load_reader("moe.experts_touched_mean",
                        Path(__file__).resolve().parents[2])
