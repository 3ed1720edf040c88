"""Share of the traced slice's device time that the prefill chunks' latent
attend takes on the layers with an indexer: ``mla.chunk_attend_share``'s
reader as it stands (the operations the prefill programs staged under
``attn.latent_chunk``, over the device's busy time in the slice), under a
name of this cell's: that accepted entry lists ``axk1-ep16-longdoc-decode``
alone and an accepted test holds it to that. Here the scope holds
engine/kvcache.py ``latent_sparse_chunk``: an admission's queries score the
index keys of the span behind them (``attn.index``), choose their rows
(``attn.select``) and gather and attend those alone (``attn.sparse_chunk``).
What an admission behind a long cached document costs the streams that
decode: lower is better (the decompressed walk over the whole span that this
form replaced took a quarter to a third of the device: PERF.md section 6).
None where the program names no such scope."""

from pathlib import Path

from harness import spec

read = spec.load_reader("mla.chunk_attend_share",
                        Path(__file__).resolve().parents[2])
