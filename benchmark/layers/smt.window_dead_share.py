"""Share of the K/V pool's held tokens that NO WINDOW layer reads any more,
for the cell whose window layers are nine of twelve: ``swa.window_dead_share``'s
reader as it stands (over the window's decode launches, the live streams'
cached tokens behind the attention window over all their cached tokens, times
the window layers' share of the pool's layers), under a name of this cell's:
that accepted entry lists its cells, and appending one to it is a
``benchmark`` PR's (PERF.md section 7). What per-kind block tables (ROADMAP
B-mech 5) would free."""

from pathlib import Path

from harness import spec

read = spec.load_reader("swa.window_dead_share",
                        Path(__file__).resolve().parents[2])
