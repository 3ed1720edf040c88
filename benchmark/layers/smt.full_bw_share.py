"""The FULL-attention layers' decode attention against the HBM roofline, for
the cell whose rows are F W W W (three full layers of twelve, no positional
encoding, contexts to 13.3 k): ``swa.full_bw_share``'s reader as it stands
(the family's ``kv_bytes_per_token`` and ``attn_flops``, which count its full
layers alone, of the traced slice's ``attended_tokens`` over the device time
of the decode programs' operations staged under ``attn.paged_decode``), under
a name of this cell's: that accepted entry lists its cells, and appending one
to it is a ``benchmark`` PR's (PERF.md section 7)."""

from pathlib import Path

from harness import spec

read = spec.load_reader("swa.full_bw_share",
                        Path(__file__).resolve().parents[2])
