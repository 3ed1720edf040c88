"""Rows an expert's bytes are paid for, for the cell that holds a layer's 64
experts whole: ``lfm2.expert_rows_mean``'s reader as it stands (the flight
ring's ``local_assignments`` over ``experts_touched``, both counted on the
device and summed over the expert blocks and steps of a launch), under a
name of this cell's: that accepted entry lists its cells, and appending one
to it is a ``benchmark`` PR's (PERF.md section 7). About 3 where 32 rows
choose 6 of 64 and nearly every expert is touched: the grouped kernel runs
every row against every touched expert, rows / this times the useful
products."""

from pathlib import Path

from harness import spec

read = spec.load_reader("lfm2.expert_rows_mean",
                        Path(__file__).resolve().parents[2])
