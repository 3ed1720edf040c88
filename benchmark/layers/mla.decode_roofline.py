"""The latent decode attention against its roofline: the bytes of every
latent row the traced slice's decode launches attended (the flight ring's
``attended_tokens``, counted when a launch was enqueued; the family's
``kv_bytes_per_token``: 576 elements a row a layer, whatever lanes a pool pads
them to, so a padded pool reads lower, rightly), q read and the output
written, and the flops of the same pairs in the PUBLISHED form (the family's
``attn_flops``: the smaller of the two forms' counts), over the device time of
the decode programs' operations staged under ``attn.latent_decode``
(engine/kvcache.py: the latent kernel, or its XLA form), against the chip's
peaks. Rows are the slice's by their drain, as ``moe.expert_bw_share``'s.
None where the program names no such scope (every other configuration, and
the parent) or the ring counted nothing."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)attn\.latent_decode(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    if win is None or not rows:
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    held = ll.flight(ctx, *win, ("decode", "decode_n"))
    attended = sum(r.get("attended_tokens") or 0 for r in held)
    tokens = sum((r.get("live_slots") or 0) * r["steps"] for r in held)
    if not seconds or not attended:
        return None
    cell = ctx["cell"]
    need = work.paged_decode_attn(cell.family, cell.published,
                                  cell.config["engine"], attended, tokens)
    return ll.share_of_roofline(need, seconds, ctx)
