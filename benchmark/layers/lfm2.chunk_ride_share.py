"""Of the window's admissions whose last chunk took a program of at most
``RIDE_ROWS`` rows, the share whose chunk RODE the decode step, for the cell
whose model is a FAMILY's (PR 61: ``models.lfm2.forward`` takes the chunk's
rows and the step's as one batch, so a layer's 32 experts are read once for
both): ``runner.chunk_ride_share``'s reader as it stands (the flight ring's
``decode_chunk`` rows over those plus the ``prefill_chunk`` rows of such a
bucket), under a name of this cell's: that accepted entry lists its cells,
and appending one to it is a ``benchmark`` PR's (PERF.md section 7: it then
deletes this file, as ``lfm2.expert_bw_share``'s). None on the parent, whose
ring holds no ``decode_chunk`` row in this cell."""

from pathlib import Path

from harness import spec

read = spec.load_reader("runner.chunk_ride_share",
                        Path(__file__).resolve().parents[2])
