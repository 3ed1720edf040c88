"""The indexer's scoring against the HBM roofline: the bytes a full layer's
decode step must move to score its context (the family's ``index_bytes``: the
index key of EVERY cached token the traced slice's decode launches attended,
the flight ring's ``attended_tokens``, 128 elements a full layer, and the
steps' index queries with their heads' weights), over the device time of the
decode programs' operations staged under ``attn.index`` (engine/kvcache.py
``latent_sparse_decode``: the keys gathered through the tables, the heads'
products, the weighted ReLU sum), against the chip's peak bandwidth. What the
selection costs BEFORE it saves anything: the one read that still grows with
the context. An XLA gather that copies the keys before it multiplies them
reads a half or less here. Rows are the slice's by their drain, as
``moe.expert_bw_share``'s. None where the program names no such scope (every
other configuration, and the parent), the ring counted nothing or the family
prices no index."""

import re

from harness import layerlib as ll
from harness import work

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = re.compile(r"(^|/)attn\.index(/|$)")


def read(ctx):
    win = ll.trace_window(ctx)
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    cell = ctx["cell"]
    if win is None or not rows or not hasattr(cell.family, "index_bytes"):
        return None
    seconds = sum(sec for program, scope, _, sec in rows
                  if re.search(PROGRAMS, program) and SCOPE.search(scope))
    held = ll.flight(ctx, *win, ("decode", "decode_n"))
    attended = sum(r.get("attended_tokens") or 0 for r in held)
    tokens = sum((r.get("live_slots") or 0) * r["steps"] for r in held)
    if not seconds or not attended:
        return None
    need = {"bytes": cell.family.index_bytes(
        cell.published, attended, tokens,
        work.KV_BYTES[cell.config["engine"].get("kv_dtype", "bfloat16")])}
    return ll.share_of_roofline(need, seconds, ctx)
