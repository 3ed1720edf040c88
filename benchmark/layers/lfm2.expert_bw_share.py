"""The routed experts' matmuls against the HBM roofline, for the cell that
holds a layer's experts WHOLE: ``moe.expert_bw_share``'s reader as it stands
(the bytes of the experts the traced slice's decode launches TOUCHED, the
flight ring's ``experts_touched`` priced by the family's ``expert_bytes``,
over the device time of the decode programs' operations staged under
``moe/experts``, against the chip's peak bandwidth), under a name of this
cell's: that accepted entry lists its cells, and appending one to it is a
``benchmark`` PR's (PERF.md section 7: it then deletes this file, as
``swa.expert_bw_share``'s). Here every one of a block's 32 experts is touched
at every step, each for ``lfm2.expert_rows_mean`` rows: the grouped kernel's
roofline share where its rows an expert are a deployment's."""

from pathlib import Path

from harness import spec

read = spec.load_reader("moe.expert_bw_share",
                        Path(__file__).resolve().parents[2])
