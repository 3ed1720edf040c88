"""Share of the traced slice's device time that the prefill chunks'
decompressed latent attend takes: the operations the prefill programs staged
under ``attn.latent_chunk`` (engine/kvcache.py: the walk over the span a chunk
has, the rows' keys and values rebuilt under ``mla/kv_b`` inside it), over
the device's busy time in the slice. What an admission behind a long cached
document costs the streams that decode: lower is better. None where the
program names no such scope (every other configuration, and the parent)."""

import re

PROGRAMS = r"prefill"       # jit__prefill_paged_fn
SCOPE = re.compile(r"(^|/)attn\.latent_chunk(/|$)")


def read(ctx):
    trace = ctx.get("trace") or {}
    rows = trace.get("op_rows") or ()
    busy = trace.get("busy_s")
    if not rows or not busy:
        return None
    if not any(SCOPE.search(scope) for _, scope, _, _ in rows):
        return None
    return 100.0 * sum(
        sec for program, scope, _, sec in rows
        if re.search(PROGRAMS, program) and SCOPE.search(scope)) / busy
