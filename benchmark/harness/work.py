"""Operations and bytes the algorithm needs, from the configuration's shapes.

Functions of the published keys (``num_hidden_layers``, ``hidden_size``, ...)
and of token counts the CLIENT saw: an output token k of a request with
prompt p attends to p + k cached tokens. Nothing here reads the program or the
compiler: a fused or recomputed byte is not a needed byte.

Hand arithmetic these are checked against (benchmark/tests/test_work.py):
Mistral-7B-v0.3 7.25 B parameters, 128 KiB of bf16 KV per token;
Mistral-Small-24B 23.6 B parameters, 160 KiB per token.
"""

from __future__ import annotations

KV_BYTES = {"bfloat16": 2.0, "float32": 4.0, "int8": 1.0, "int4": 0.5}
WEIGHT_BYTES = {None: 2.0, "": 2.0, "int8": 1.0, "int8_w8a8": 1.0,
                "int4": 0.5}


def head_dim(hf: dict) -> int:
    return int(hf.get("head_dim")
               or hf["hidden_size"] // hf["num_attention_heads"])


def layer_params(hf: dict) -> int:
    """Matmul weights of one decoder layer (norm gains left out: 2 D)."""
    d, f, hd = hf["hidden_size"], hf["intermediate_size"], head_dim(hf)
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def param_count(hf: dict) -> int:
    """Every weight: layers, embedding table, output head (unless tied)."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    head = 0 if hf.get("tie_word_embeddings") else d * v
    norms = (2 * hf["num_hidden_layers"] + 1) * d
    return hf["num_hidden_layers"] * layer_params(hf) + d * v + head + norms


def step_params(hf: dict) -> int:
    """Weights one forward pass of one token position reads or multiplies:
    all layers and the output head. The embedding table is gathered (one
    row a token), not read."""
    return (hf["num_hidden_layers"] * layer_params(hf)
            + hf["hidden_size"] * hf["vocab_size"])


def kv_bytes_per_token(hf: dict, kv_dtype: str = "bfloat16") -> float:
    """K and V of one token over all layers."""
    return (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"]
            * head_dim(hf) * KV_BYTES[kv_dtype])


def attn_flops(hf: dict, pairs: int) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, all
    layers: 2 matmuls x 2 flops x heads x head_dim each."""
    return (4.0 * hf["num_hidden_layers"] * hf["num_attention_heads"]
            * head_dim(hf) * pairs)


def decode_bytes(hf: dict, engine: dict, steps: int, attended: int) -> float:
    """Bytes ``steps`` decode steps must move: the weights once a step
    (whatever the batch), and the K/V of every attended token once."""
    wbytes = WEIGHT_BYTES[engine.get("quantization")]
    kv = kv_bytes_per_token(hf, engine.get("kv_dtype", "bfloat16"))
    return steps * step_params(hf) * wbytes + attended * kv


def prefill_flops(hf: dict, tokens: int, pairs: int) -> float:
    """Matmul flops of ``tokens`` prompt positions (2 per weight) plus causal
    attention over ``pairs`` pairs. The output head runs once a request, not
    once a token: left out (under 1% at these prompt lengths)."""
    return (2.0 * hf["num_hidden_layers"] * layer_params(hf) * tokens
            + attn_flops(hf, pairs))


def causal_pairs(prompt_tokens: int) -> int:
    """Pairs a causal prefill of p tokens attends: p (p + 1) / 2."""
    return prompt_tokens * (prompt_tokens + 1) // 2


def paged_decode_attn(hf: dict, engine: dict, attended: int,
                      tokens: int) -> dict:
    """The paged decode kernel's needs over calls that attend ``attended``
    cached tokens for ``tokens`` query tokens: K/V bytes read once, q read
    and the output written, and the two matmuls' flops."""
    kv = kv_bytes_per_token(hf, engine.get("kv_dtype", "bfloat16"))
    qo = (2 * 2.0 * hf["num_hidden_layers"] * hf["num_attention_heads"]
          * head_dim(hf))
    return {"bytes": attended * kv + tokens * qo,
            "flops": attn_flops(hf, attended)}


def roofline_seconds(work: dict, peak: dict, chips: int = 1) -> tuple[float,
                                                                      str]:
    """Least time ``chips`` chips could take, and which bound sets it."""
    t_b = work.get("bytes", 0.0) / (peak["hbm_bytes_s"] * chips)
    t_f = work.get("flops", 0.0) / (peak["bf16_flops"] * chips)
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
