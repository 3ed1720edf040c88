"""Operations and bytes the algorithm needs: what is architecture-free.

What a forward pass multiplies, reads and caches is the configuration's
family's to say (``benchmark/reference/<family>.py``, the contract in
harness/spec.py): functions of the published keys and of token counts the
CLIENT saw (an output token k of a request with prompt p attends to p + k
cached tokens). Here are the byte tables, the compositions the readers ask
for and the roofline. Nothing here reads a published key, the program or the
compiler: a fused or recomputed byte is not a needed byte.
"""

from __future__ import annotations

from typing import Iterable

KV_BYTES = {"bfloat16": 2.0, "float32": 4.0, "int8": 1.0, "int4": 0.5}
WEIGHT_BYTES = {None: 2.0, "": 2.0, "int8": 1.0, "int8_w8a8": 1.0,
                "int4": 0.5}


def kv_bytes_per_token(family, hf: dict, engine: dict) -> float:
    """K and V of one token over all layers, in the engine's cache type."""
    return family.kv_bytes_per_token(
        hf, KV_BYTES[engine.get("kv_dtype", "bfloat16")])


def decode_bytes(family, hf: dict, engine: dict,
                 dispatches: Iterable[tuple[int, float]],
                 attended: int) -> float:
    """Bytes decode dispatches of (steps, query tokens) each must move: the
    weights a step over its share of the query tokens reads, once a step,
    and the K/V of every attended token once."""
    weights = sum(steps * family.step_params(hf, tokens / steps)
                  for steps, tokens in dispatches if steps)
    return (weights * WEIGHT_BYTES[engine.get("quantization")]
            + attended * kv_bytes_per_token(family, hf, engine))


def prefill_flops(family, hf: dict, tokens: float, pairs: float) -> float:
    """Matmul flops of ``tokens`` prompt positions (2 per weight a token
    multiplies) plus causal attention over ``pairs`` pairs."""
    return (2.0 * family.token_params(hf) * tokens
            + family.attn_flops(hf, pairs))


def causal_pairs(prompt_tokens: int) -> int:
    """Pairs a causal prefill of p tokens attends: p (p + 1) / 2."""
    return prompt_tokens * (prompt_tokens + 1) // 2


def paged_decode_attn(family, hf: dict, engine: dict, attended: int,
                      tokens: int) -> dict:
    """The paged decode kernel's needs over calls that attend ``attended``
    cached tokens for ``tokens`` query tokens: K/V bytes read once, q read
    and the output written (bfloat16), and the two matmuls' flops."""
    qo = 2 * 2.0 * family.q_elements_per_token(hf)
    return {"bytes": (attended * kv_bytes_per_token(family, hf, engine)
                      + tokens * qo),
            "flops": family.attn_flops(hf, attended)}


def roofline_seconds(work: dict, peak: dict, chips: int = 1) -> tuple[float,
                                                                      str]:
    """Least time ``chips`` chips could take, and which bound sets it."""
    t_b = work.get("bytes", 0.0) / (peak["hbm_bytes_s"] * chips)
    t_f = work.get("flops", 0.0) / (peak["bf16_flops"] * chips)
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
