"""The routed experts' matmuls against the HBM roofline, for the cell whose
experts are ReLU-gated, chosen by a router in front of attention and held
WHOLE (64 a layer, nearly every one touched by a step's 32 rows):
``moe.expert_bw_share``'s reader as it stands (the bytes of the experts the
traced slice's decode launches TOUCHED, the flight ring's
``experts_touched`` priced by the family's ``expert_bytes``, over the device
time of the decode programs' operations staged under ``moe/experts``, against
the chip's peak bandwidth), under a name of this cell's: that accepted entry
lists its cells, and appending one to it is a ``benchmark`` PR's (PERF.md
section 7: it then deletes this file, as ``swa.expert_bw_share``'s and
``lfm2.expert_bw_share``'s). The grouped kernel with the ReLU gate at a whole
chip's expert load."""

from pathlib import Path

from harness import spec

read = spec.load_reader("moe.expert_bw_share",
                        Path(__file__).resolve().parents[2])
