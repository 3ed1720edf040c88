"""Of the window's admissions whose last chunk took a program of at most
``RIDE_ROWS`` rows, the share whose chunk RODE the decode step, for the cell
whose model is the DeltaNet hybrid (PR 64: ``models.qwen3_next.forward``
takes the chunk's rows and the step's as one batch, so a layer's
projections and its 64 held experts are read once for both, and the chunk's
recurrence walks its real rows inside that one program):
``runner.chunk_ride_share``'s reader as it stands (the flight ring's
``decode_chunk`` rows over those plus the ``prefill_chunk`` rows of such a
bucket), under a name of this cell's: that accepted entry lists its cells,
and appending one to it is a ``benchmark`` PR's (PERF.md section 7: it then
deletes this file, as ``lfm2.chunk_ride_share``'s). None on the parent, whose
ring holds no ``decode_chunk`` row in this cell."""

from pathlib import Path

from harness import spec

read = spec.load_reader("runner.chunk_ride_share",
                        Path(__file__).resolve().parents[2])
