"""Share of the decode programs' device time that moves the KV pool around:
the operations the program staged under a ``kv_pool.*`` scope
(engine/kvcache.py), plus the copies and restacks XLA adds around them, which
carry no scope or only the scan's and are known by their result's shape: the
pool's [cache layers, blocks, kv heads, block tokens, head dim] or one layer's
slice of it, in any order of dimensions, worked out from the cell's
configuration and its family (``cache_layers``, harness/spec.py).
The layer metric of "write the pool in place" (ROADMAP A1). None where the
program names no scope."""

import re

from harness import trace_reduce

PROGRAMS = r"decode"        # jit__decode_paged_fn, jit__decode_paged_n_fn
SCOPE = "kv_pool."
DIMS = re.compile(r"\[([\d,]*)\]$")


def pool_dims(cell) -> set[tuple[int, ...]]:
    """The pool's dimensions and one layer's, each sorted: the paged pool of
    an attention that caches K and V a kv head (this reader's own knowledge
    of engine/kvcache.py's layout, not the harness's of an architecture)."""
    hf, engine = cell.published, cell.config["engine"]
    if "kv_num_blocks" not in engine:
        return set()        # sized by the program: only the scopes tell
    # a chip holds its share of the kv heads (the trace is a chip's)
    tp = int((cell.config.get("sharding") or {}).get(
        "tensor_parallel_size") or 1)
    layer = (int(engine["kv_num_blocks"]), hf["num_key_value_heads"] // tp,
             int(engine.get("kv_block_tokens", 64)),    # the engine's default
             int(hf.get("head_dim")
                 or hf["hidden_size"] // hf["num_attention_heads"]))
    # the pool's leading dimension is the family's to say, where a layer's
    # weights cache more than once a token
    # (tier-1's tests/test_bench_trace.py reads with a cell that has no
    # family)
    cache_layers = getattr(getattr(cell, "family", None), "cache_layers",
                           None)
    layers = int(cache_layers(hf)) if cache_layers else (
        hf["num_hidden_layers"])
    return {tuple(sorted(layer)), tuple(sorted((layers,) + layer)),
            tuple(sorted((1,) + layer))}


def read(ctx):
    rows = (ctx.get("trace") or {}).get("op_rows") or ()
    if not any(SCOPE in scope for _, scope, _, _ in rows):
        return None
    seconds, _ = trace_reduce.module_seconds(ctx["trace"], PROGRAMS)
    if not seconds:
        return None
    shapes = pool_dims(ctx["cell"])

    def moves(scope: str, short: str) -> bool:
        dims = DIMS.search(short)
        return SCOPE in scope or (dims is not None and tuple(sorted(
            int(d) for d in dims[1].split(",") if d)) in shapes)

    return 100.0 * sum(sec for program, scope, short, sec in rows
                       if re.search(PROGRAMS, program)
                       and moves(scope, short)) / seconds
