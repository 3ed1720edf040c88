"""The routed experts' matmuls against the HBM roofline, for the cell of
window and full attention layers: ``moe.expert_bw_share``'s reader as it
stands (the bytes of the experts the traced slice's decode launches TOUCHED,
the flight ring's ``experts_touched`` priced by the family's ``expert_bytes``,
over the device time of the decode programs' operations staged under
``moe/experts``, against the chip's peak bandwidth), under a name of this
cell's: that accepted entry lists ``qn80-ep8-decode`` alone, and appending a
cell to it is a ``benchmark`` PR's (PERF.md section 7: it then deletes this
file). The third of the three shares that say where such a cell's step goes
(window layers, full layers, experts)."""

from pathlib import Path

from harness import spec

read = spec.load_reader("moe.expert_bw_share",
                        Path(__file__).resolve().parents[2])
