"""Test data, no model's: the dense decoder of ``llama_family`` with the order
of its layers SPELLED OUT as a ``walk`` (every row of the one stack once, in
order, which is what a family without a walk gets). A configuration that
names it must be judged exactly as one that names ``llama_family``."""

from reference.llama_family import (attn_flops, decoder_layer,  # noqa: F401
                                    kv_bytes_per_token, layer_params, logits,
                                    param_count, q_elements_per_token,
                                    rope_tables, step_params, token_params)


def walk(x, layer, rows, leaf, hf):
    for index in range(rows):
        x = layer(x, index)
    return x
