"""The WINDOW layers' decode attention against the HBM roofline, for the
cell whose rows are F W W W (nine window layers of twelve, 28 query heads in
groups of 7): ``swa.window_bw_share``'s reader as it stands (the family's
``window_bytes`` / ``window_flops`` of the traced slice's ``window_tokens``
over the device time of the decode programs' operations staged under
``attn.window_decode``), under a name of this cell's: that accepted entry
lists its cells, and appending one to it is a ``benchmark`` PR's (PERF.md
section 7)."""

from pathlib import Path

from harness import spec

read = spec.load_reader("swa.window_bw_share",
                        Path(__file__).resolve().parents[2])
