"""Test data, no model's: the dense decoder of ``llama_family`` with its stack
run TWICE a token, the final norm between the passes (``logits`` applies it
after the last), and a cache entry for every (pass, layer) pair. The weights
are the dense decoder's, so the parameter count is; no program serves this:
against a server that runs the stack once the reference check must FAIL."""

from reference.llama_family import (attn_flops, decoder_layer,  # noqa: F401
                                    kv_bytes_per_token, layer_params, logits,
                                    norm_eps, param_count,
                                    q_elements_per_token, rms_norm,
                                    rope_tables, step_params, token_params)

PASSES = 2


def walk(x, layer, rows, leaf, hf):
    for turn in range(PASSES):
        if turn:
            x = rms_norm(x, leaf("final_norm"), norm_eps(hf))
        for index in range(rows):
            x = layer(x, index)
    return x


def cache_layers(hf: dict) -> int:
    return PASSES * hf["num_hidden_layers"]
