#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call:

  * kernel phase — ONE child process on the chip compiles (never interprets)
    every Pallas entry point the selectors in localai_tpu.ops answer "pallas"
    for at the Llama-3-8B head shapes, and compares each with its lax
    reference on seeded random data; where the paged kernel also WRITES the
    decode step's rows (unscaled pools), the pool it hands back with the
    policy's scatter's, exactly (``--chips 4``: one more child runs that
    case under ``shard_map`` over the four chips, each writing its heads);
  * server phase — ``python -m localai_tpu.cli.main run`` as a child, serving
    ``debug:llama3-8b`` int8 at all 32 layers and published widths, asserted
    from outside over HTTP: the devices it reports, a handful of
    /v1/chat/completions requests, /metrics, /debug/devices, a clean SIGTERM.

    python chip_smoke.py              # one chip (pinned to chip 0)
    python chip_smoke.py --chips 4    # four chips: auto mesh tp=4, then four
                                      # pinned one-chip workers behind the
                                      # fleet router

Contract: this parent never imports JAX (a chip belongs to one process at a
time; every phase is a child, run one after another). It accepts nothing but
the expected platform — children get JAX_PLATFORMS set explicitly, so with no
TPU it fails within seconds instead of serving from the CPU. Any failed
assertion or child exit code ends it non-zero with no result line; nothing is
caught and turned into a report line. It reads no state from outside the
tree (the JAX compilation cache its children share is a temp dir created for
the run and removed after it). Every line it prints
names platform, device_kind and device count; the last line of a passing run
is one JSON object with exactly the keys the driver reads,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It measures nothing: the summary it writes (report.json, and the line before
the last) ends with "claim": null.

The steps are functions so tests/test_chip_smoke.py can drive them at
debug:tiny on the CPU.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib.util
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Llama-3-8B attention shapes (models.registry DEBUG_PRESETS["llama3-8b"])
HEADS = {"num_heads": 32, "num_kv_heads": 8, "head_dim": 128}

# The looped decoder's shape of the paged kernel (benchmark/configs/
# ouro-2.6b-int8.json): 16 query = 16 kv heads (of HEADS' head_dim), a pool
# of 4 passes x 48 layers, read at both ends of the first two passes
LOOPED = {"heads": 16, "cache_layers": 192, "picks": [0, 47, 48, 191]}
# and the looped tiny model the second server phase serves: debug:tiny-loop,
# 2 layers run 3 times a token (models.registry.DEBUG_PRESETS)
LOOPED_SERVER = dict(model="debug:tiny-loop", context=512, slots=4,
                     long_prompt=200, engine={"prefill_chunk": 64},
                     tag="looped")

# The context the one-chip server phase serves. Largest that fits every
# program the scheduler dispatches on a 15.75 GiB v5e chip, from compiling
# the runner's programs for the v5e topology (PERF.md "Bring-up"):
#   weights, int8 8B                                   7.48 GiB
#   bf16 KV pool = 8 slots x ctx x 128 KiB/token       1 MiB per ctx token
#     (2 x 32 layers x 8 kv heads x 128 x 2 B = 128 KiB per token per slot)
#   temp of decode_n(n=16) = one MORE pool (the layer scan in
#     models.llama.forward carries the pool as xs -> ys) + 0.76 GiB
#   ctx 2048: 7.48 + 2.02 + 2.89 = 12.39 GiB   fits, 3.4 GiB spare
#   ctx 3072: 7.48 + 3.02 + 3.95 = 14.46 GiB   compiles, but the scheduler
#     keeps two dispatches in flight and a second temp does not fit
#   ctx 4096:                      16.4  GiB   RESOURCE_EXHAUSTED
CONTEXT = 2048
SLOTS = 8

# Every request is greedy and ignores EOS, so it returns exactly max_tokens
# tokens; the logit bias lifts the 26 lowercase ASCII letters above every
# other id, so each generated token is one visible byte of the reply (a
# 128k-vocab model over the byte tokenizer otherwise decodes to almost
# nothing) and "same tokens" can be read off the text. The logits are still
# the full model's: the argmax runs over 26 of them.
LETTERS = {str(i): 100.0 for i in range(ord("a"), ord("z") + 1)}


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Smoke:
    """What the run knows so far: the device (once a child has reported
    it), the output directory, and the env every child starts from."""

    def __init__(self, out_dir: Path, expect_platform: str = "tpu"):
        self.expect_platform = expect_platform
        self.device: dict = {}
        self.out_dir = out_dir
        out_dir.mkdir(parents=True, exist_ok=True)
        # shared by the children of this run only; serialized 8B programs
        # are tens of MiB each, so not under the (copied-back) out_dir
        self.cache_dir = Path(tempfile.mkdtemp(prefix="chip_smoke_jax_"))
        self.report: dict = {"phases": {}}

    def close(self) -> None:
        shutil.rmtree(self.cache_dir, ignore_errors=True)

    def say(self, msg: str) -> None:
        d = self.device
        print(f"[platform={d.get('platform', '?')} "
              f"kind={d.get('kind', '?')} count={d.get('count', '?')}] "
              f"{msg}", flush=True)

    def child_env(self, extra: dict | None = None) -> dict:
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": self.expect_platform,
            "JAX_COMPILATION_CACHE_DIR": str(self.cache_dir),
            "PYTHONPATH": str(ROOT),
            "PYTHONUNBUFFERED": "1",
        })
        env.update(extra or {})
        return env

    def one_chip_env(self) -> dict:
        """Pin a child to chip 0, so that four visible chips do not turn the
        one-chip leg into the meshed leg — the fleet's own recipe
        (localai_tpu.fleet.pinning), loaded by path: importing the package
        would import JAX into this parent."""
        if self.expect_platform != "tpu":
            return {}
        spec = importlib.util.spec_from_file_location(
            "_pinning", ROOT / "localai_tpu" / "fleet" / "pinning.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.tpu_process_env([0])

    def save_report(self) -> None:
        (self.out_dir / "report.json").write_text(
            json.dumps(self.report, indent=2, sort_keys=True) + "\n")


# ---------------------------------------------------------------------------
# kernel phase — the child body (the only code here that imports JAX)
# ---------------------------------------------------------------------------

# Tolerance of every kernel-vs-reference comparison: |got - ref| <= TOL +
# TOL * |ref|. Inputs are N(0,1): an attention output is a convex
# combination of V rows (|out| up to ~4.5 where one row dominates), a
# matmul output is ~N(0, 1.3). The kernels write bf16 and the reference is
# f32 at "highest" matmul precision, so the floor is the output rounding:
# half a bf16 ulp, 2^-9 relative — 0.0156 at |out| in [4, 8), which is
# exactly the largest error measured on a v5e (PERF.md, PR 21). On top of
# it the MXU may round f32 operands (q after scaling, the probabilities) to
# bf16 once: measured <= 0.013 at |out| < 2. A wrong kernel — a mis-walked
# block table, a wrong mask edge, a dropped or mis-indexed scale row — is
# off by O(0.1..1) on most elements. 2e-2 (+2% of |ref|) separates the two.
TOL = 2e-2


def kernel_child(spec: dict) -> int:
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != spec["expect_platform"]:
        print(f"kernel phase: JAX came up on {device}, not on "
              f"{spec['expect_platform']!r}", file=sys.stderr)
        return 3

    from localai_tpu import ops
    from localai_tpu.engine.paged import block_tokens_default
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.quant import (dequantize_tensor,
                                          quantize_lastdim,
                                          quantize_lastdim4,
                                          quantize_tensor, quantize_tensor4)
    from localai_tpu.ops import qmatmul

    interpret = bool(spec["interpret"])
    want = "pallas_interpret" if interpret else "auto"
    S, ctx = spec["slots"], spec["context"]
    Hq, Hkv, hd = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    bt = block_tokens_default()      # the block size the runner would choose
    rng = np.random.default_rng(spec["seed"])
    bf16 = jnp.bfloat16

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf16)

    def f32(x):
        return jnp.asarray(x, jnp.float32)

    cases = []

    def finish() -> int:
        report = {"device": device, "interpret": interpret, "tolerance": TOL,
                  "block_tokens": bt, "cases": cases}
        Path(spec["report"]).write_text(json.dumps(report, indent=2) + "\n")
        return 0 if all(c["ok"] for c in cases) else 4

    def run(name, kernel, reference, *args, tol=TOL):
        t0 = time.monotonic()
        got = np.asarray(jax.block_until_ready(jax.jit(kernel)(*args)),
                         np.float32)
        seconds = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(reference)(*args), np.float32)
        err = float(np.max(np.abs(got - ref)))
        ok = (got.shape == ref.shape and bool(np.all(np.isfinite(got)))
              and bool(np.allclose(got, ref, atol=tol, rtol=tol)))
        cases.append({"case": name, "ok": ok, "max_abs_err": round(err, 5),
                      "shape": list(got.shape),
                      "compile_and_run_s": round(seconds, 2)})
        if not ok:      # stdout belongs to the parent's device-named lines
            print(f"kernel {name} FAILED: max_abs_err={err:.5f} (tolerance "
                  f"{TOL})", file=sys.stderr, flush=True)

    def selected(select, **kw):
        impl, interp = select(want, backend=device["platform"], **kw)
        if (impl, interp) != ("pallas", interpret):
            raise SystemExit(f"selector answered {(impl, interp)} for {kw}")

    # -- paged decode: the serving path's kernel -------------------------
    MB = ctx // bt
    N = S * MB + 1                                   # + the trash block
    # physically scattered tables, and frontiers on every kind of edge:
    # first row, last row of a block, first row of the next, mid-context,
    # the last two rows of the context
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N))[:S * MB].reshape(S, MB), jnp.int32)
    edges = [0, 1, bt - 1, bt, 2 * bt + 3, ctx // 2, ctx - 2, ctx - 1]
    positions = jnp.asarray([edges[i % len(edges)] for i in range(S)],
                            jnp.int32)

    def writes_case(name, q, k, v, layer, tabs, pos, wrap=lambda f: f,
                    live=None, window=None):
        """The kernel as the decode step's WRITER (unscaled pools): handed
        the pool of before the step and the step's rows, its output against
        the reference over the pool the policy's scatter
        (``kvcache._write_rows``) leaves, and the two pools it hands back
        against that pool, EXACTLY, in every layer outside the trash block
        (largest difference 0). ``wrap``: the kernel under a mesh. ``live``
        [S] bool: the slots that hold a stream, where not all do (the
        others' rows of the output are zeros). ``window``: the layer's
        sliding window."""
        from localai_tpu.engine import kvcache as kvc

        k_new, v_new = (normal((q.shape[0], k.shape[2], k.shape[-1]))
                        for _ in range(2))
        kernel = wrap(lambda *a: ops.paged_decode_attention(
            *a, sliding_window=window, interpret=interpret))

        def written(q, k, v, tabs, pos, k_new, v_new):
            return kernel(q, k, v, jnp.int32(layer), tabs, pos, None, None,
                          k_new, v_new)

        def scattered(q, k, v, tabs, pos, k_new, v_new):
            blk = tabs[jnp.arange(q.shape[0]), pos // bt]
            return kvc._write_rows((k, v), jnp.int32(layer), blk, pos % bt,
                                   k_new, v_new)

        def apart(q, k, v, *rest):
            return jnp.stack([
                jnp.max(jnp.abs(f32(a[:, 1:]) - f32(b[:, 1:])))
                for a, b in zip(written(q, k, v, *rest)[1:],
                                scattered(q, k, v, *rest))])

        def reference(q, k, v, tabs, pos, *rows):
            k2, v2 = scattered(q, k, v, tabs, pos, *rows)
            ref = ops.paged_decode_attention_ref(
                q, k2[layer], v2[layer], tabs, pos, sliding_window=window)
            return ref if live is None else jnp.where(
                live[:, None, None], ref, 0)

        args = (q, k, v, tabs, pos, k_new, v_new)
        run(f"{name} writes: output", lambda *a: written(*a)[0], reference,
            *args)
        run(f"{name} writes: pool against the scatter's", apart,
            lambda *a: jnp.zeros(2), *args, tol=0.0)

    if spec.get("mesh"):
        # -- four chips: the kernel under shard_map as the meshed runner
        # wraps it, heads on 'model': each chip writes its own heads' rows
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(devices[:spec["mesh"]]).reshape(1, -1),
                    ("data", "model"))
        rows, pool = P("data", "model", None), P(None, None, "model", None,
                                                 None)
        MB = min(spec["context"] // bt, 8)
        N = S * MB + 1
        tabs = jnp.asarray(rng.permutation(np.arange(1, N))[:S * MB]
                           .reshape(S, MB), jnp.int32)
        pos = jnp.asarray([(i * (bt + 5)) % (MB * bt) for i in range(S)],
                          jnp.int32)
        writes_case(
            f"paged_decode bfloat16 bt={bt} hd={hd} on {spec['mesh']} chips",
            normal((S, Hq, hd)), normal((2, N, Hkv, bt, hd)),
            normal((2, N, Hkv, bt, hd)), 1, tabs, pos,
            wrap=lambda f: shard_map(
                f, mesh=mesh, in_specs=(
                    rows, pool, pool, P(), P("data", None), P("data"), None,
                    None, rows, rows),
                out_specs=(rows, pool, pool), check_vma=False))
        return finish()

    def paged_case(kv_dtype, Hq, Hkv, hd):
        selected(ops.select_paged_attn_impl, num_heads=Hq, num_kv_heads=Hkv,
                 head_dim=hd, block_tokens=bt, kv_dtype=kv_dtype)
        q = normal((S, Hq, hd))
        k, v = normal((N, Hkv, bt, hd)), normal((N, Hkv, bt, hd))
        extra = ()
        if kv_dtype != "bfloat16":
            quant = quantize_lastdim4 if kv_dtype == "int4" else quantize_lastdim
            (k, ks), (v, vs) = quant(k), quant(v)
            extra = (ks, vs)

        # the kernel takes the pool stacked over layers and a layer index
        # (in the served programs the pool is the layer scan's carry)
        def paged_kernel(q, k, v, tables, positions, *scales):
            return ops.paged_decode_attention(
                q, k[None], v[None], jnp.int32(0), tables, positions,
                *(x[None] for x in scales), interpret=interpret)

        run(f"paged_decode {kv_dtype} bt={bt} hd={hd}", paged_kernel,
            ops.paged_decode_attention_ref,
            q, k, v, tables, positions, *extra)
        if not extra:       # an unscaled pool: the kernel writes the step
            writes_case(f"paged_decode {kv_dtype} bt={bt} hd={hd}", q,
                        jnp.stack([k, v]), jnp.stack([v, k]), 1, tables,
                        positions)

    paged_case("bfloat16", Hq, Hkv, hd)
    paged_case("int8", Hq, Hkv, hd)
    # int4 pools: the selector refuses them below head_dim 256 (their packed
    # rows are hd/2 lanes), so the nibble kernel runs at the nearest shape
    # it serves — same bytes per row as the 8B int8 pool
    paged_case("int4", Hq // 2, Hkv // 2, 2 * hd)

    # -- part-full batches (PR 50): a slot whose table row is on the trash
    # block gets no copy and no fold, its output row is zeros; the live
    # slots' rows and the pool are the reference's and the scatter's. The
    # chat cells' occupancy at their shapes: 5 live of the 7B's 16 slots,
    # every kv head in a program; 6 live of the 24B's 32, a chip's quarter;
    # and the first under a sliding window, the walks' first entries past 0
    def part_full_case(n_live, slots, Hq, Hkv, window=None):
        mb = min(MB, 8)
        n = n_live * mb + 1
        rows = np.sort(rng.permutation(slots)[:n_live])
        tabs, pos = np.zeros((slots, mb), np.int32), np.zeros(slots, np.int32)
        tabs[rows] = rng.permutation(np.arange(1, n)).reshape(n_live, mb)
        ends = edges if window is None else [
            mb * bt - 1, mb * bt - 2, mb * bt // 2 + 3, (mb - 1) * bt, bt - 1]
        pos[rows] = [min(ends[i % len(ends)], mb * bt - 1)
                     for i in range(n_live)]
        live = jnp.zeros(slots, bool).at[rows].set(True)
        tabs, pos = jnp.asarray(tabs), jnp.asarray(pos)
        q = normal((slots, Hq, hd))
        k, v = normal((2, n, Hkv, bt, hd)), normal((2, n, Hkv, bt, hd))
        name = (f"paged_decode bfloat16 bt={bt} hd={hd} {n_live} live of "
                f"{slots} slots" + (f" window {window}" if window else ""))
        run(name,
            lambda q, k, v, tabs, pos: ops.paged_decode_attention(
                q, k, v, jnp.int32(1), tabs, pos, sliding_window=window,
                interpret=interpret),
            lambda q, k, v, tabs, pos: jnp.where(
                live[:, None, None],
                ops.paged_decode_attention_ref(
                    q, k[1], v[1], tabs, pos, sliding_window=window), 0),
            q, k, v, tabs, pos)
        writes_case(name, q, k, v, 1, tabs, pos, live=live, window=window)

    part_full_case(5, 16, Hq, Hkv)
    part_full_case(6, 32, Hq // Hkv * max(Hkv // 4, 1), max(Hkv // 4, 1))
    part_full_case(5, 16, Hq, Hkv, window=bt + bt // 2)

    # -- the looped decoder's shape of the same kernel: one query row a kv
    # head (plain multi-head), and a pool whose leading dimension is passes
    # x layers, read at the first and last cache layer of two passes
    def looped_case(heads, cache_layers, picks):
        selected(ops.select_paged_attn_impl, num_heads=heads,
                 num_kv_heads=heads, head_dim=hd, block_tokens=bt,
                 kv_dtype="bfloat16")
        mb = min(MB, 4)                 # a short context: the pool is deep
        n = S * mb + 1
        tabs = jnp.asarray(rng.permutation(np.arange(1, n))[:S * mb]
                           .reshape(S, mb), jnp.int32)
        pos = jnp.minimum(positions, mb * bt - 1)
        q = normal((S, heads, hd))
        # the pool goes in as an ARGUMENT (closed over, its 2 x 1.7 GB
        # would be lowered into every program as constants)
        k = jnp.zeros((cache_layers, n, heads, bt, hd), bf16)
        v = jnp.zeros((cache_layers, n, heads, bt, hd), bf16)
        for layer in picks:
            k = k.at[layer].set(normal((n, heads, bt, hd)))
            v = v.at[layer].set(normal((n, heads, bt, hd)))
        for layer in picks:
            run(f"paged_decode looped q_per_kv=1 layer {layer} of "
                f"{cache_layers}",
                lambda q, k, v, tabs, pos, layer=layer:
                    ops.paged_decode_attention(
                        q, k, v, jnp.int32(layer), tabs, pos,
                        interpret=interpret),
                lambda q, k, v, tabs, pos, layer=layer:
                    ops.paged_decode_attention_ref(
                        q, k[layer], v[layer], tabs, pos),
                q, k, v, tabs, pos)
        writes_case(f"paged_decode looped q_per_kv=1 layer {picks[-1]} of "
                    f"{cache_layers}", q, k, v, picks[-1], tabs, pos)

    looped_case(**spec["looped"])

    # -- contiguous cache: decode + prefill (embeddings, mirrored engines) -
    selected(ops.select_attn_impl, num_heads=Hq, num_kv_heads=Hkv,
             head_dim=hd, max_ctx=ctx)
    cfg = mdl.LlamaConfig(num_heads=Hq, num_kv_heads=Hkv, head_dim=hd,
                          hidden_size=Hq * hd)

    def decode_ref(q, k, v, pos, ks=None, vs=None):
        if ks is not None:
            k, v = f32(k) * ks[..., None], f32(v) * vs[..., None]
        mask = (jnp.arange(ctx)[None, :] <= pos[:, None])[:, None, :]
        return mdl._grouped_attn(cfg, f32(q)[:, None], f32(k), f32(v),
                                 mask)[:, 0]

    q = normal((S, Hq, hd))
    k, v = normal((S, Hkv, ctx, hd)), normal((S, Hkv, ctx, hd))
    def decode_kernel(q, k, v, pos, *scales):
        return ops.decode_attention(q, k[None], v[None], jnp.int32(0), pos,
                                    *(x[None] for x in scales),
                                    interpret=interpret)

    run("decode bfloat16", decode_kernel, decode_ref, q, k, v, positions)
    (k8, ks), (v8, vs) = quantize_lastdim(k), quantize_lastdim(v)
    run("decode int8", decode_kernel, decode_ref, q, k8, v8, positions,
        ks, vs)

    for T in spec["prefill_buckets"]:
        length = jnp.int32(T - T // 3)               # a padded bucket

        def prefill_ref(q, k, v, length, T=T):
            t = jnp.arange(T)
            mask = ((t[None, :] <= t[:, None]) & (t[None, :] < length))[None]
            return mdl._grouped_attn(cfg, f32(q)[None], f32(k)[None],
                                     f32(v)[None], mask)[0]

        def prefill_kernel(q, k, v, length):
            return ops.prefill_attention(q, k, v, length,
                                         interpret=interpret)

        got_rows = T - T // 3                        # rows past length: junk
        run(f"prefill T={T}",
            lambda *a: prefill_kernel(*a)[:got_rows],
            lambda *a: prefill_ref(*a)[:got_rows],
            normal((T, Hq, hd)), normal((Hkv, T, hd)), normal((Hkv, T, hd)),
            length)

    # -- opt-in dequant matmuls (LOCALAI_W8_KERNEL): they stay, so they run -
    D, F = Hq * hd, spec["ffn"]
    x = normal((S, D))
    w = jnp.asarray(rng.standard_normal((D, F), np.float32) * 0.02, bf16)
    for name, qt, kern in (
        ("w8_matmul", quantize_tensor(w, 0),
         lambda x, q, s: qmatmul.w8_matmul(x, q, s, interpret=interpret)),
        ("w8_matmul transposed", quantize_tensor(w.T, 1),
         lambda x, q, s: qmatmul.w8_matmul(x, q, s, transpose_w=True,
                                           interpret=interpret)),
        ("w4_matmul", quantize_tensor4(w, 0),
         lambda x, q, s: qmatmul.w4_matmul(x, q, s, interpret=interpret)),
    ):
        wd = dequantize_tensor(qt)
        if "transposed" in name:
            wd = wd.T
        # products of N(0,1) x 0.02 over D=4096 terms: |y| ~ 1.3, same scale
        # as the attention outputs, same tolerance
        run(name, kern, lambda x, q, s, wd=wd: f32(x) @ wd,
            x, qt.q, qt.scale)

    return finish()


def kernel_phase(smoke: Smoke, *, context: int = CONTEXT, slots: int = SLOTS,
                 heads: dict = HEADS, ffn: int = 14336,
                 prefill_buckets=(128, 512, 2048), interpret: bool = False,
                 looped: dict = LOOPED, timeout: float = 600.0) -> dict:
    """One child on the chip: every Pallas entry point, compiled, against
    its lax reference. Returns the child's report (and learns the device)."""
    report_path = smoke.out_dir / "kernels.json"
    spec = {"expect_platform": smoke.expect_platform, "context": context,
            "slots": slots, "ffn": ffn, "interpret": interpret, "seed": 0,
            "prefill_buckets": [b for b in prefill_buckets if b <= context],
            "report": str(report_path), "looped": looped, **heads}
    smoke.say("kernel phase: starting")
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--kernel-child",
         json.dumps(spec)],
        env=smoke.child_env(smoke.one_chip_env()), timeout=timeout)
    check(proc.returncode == 0,
          f"kernel phase child exited {proc.returncode}")
    report = json.loads(report_path.read_text())
    smoke.device = report["device"]
    for c in report["cases"]:
        smoke.say(f"kernel {c['case']}: max_abs_err={c['max_abs_err']} "
                  f"(tolerance {report['tolerance']})")
    check(report["interpret"] == interpret, "kernel phase ran interpreted")
    smoke.report["phases"]["kernels"] = report
    return report


def mesh_kernel_phase(smoke: Smoke, *, chips: int, context: int = CONTEXT,
                      slots: int = SLOTS, heads: dict = HEADS,
                      interpret: bool = False, timeout: float = 600.0,
                      env: dict | None = None) -> dict:
    """One child over ``chips`` chips: the paged kernel under ``shard_map``
    as the meshed runner wraps it, writing the step's rows: each chip its
    own heads', the pool against the scatter's."""
    report_path = smoke.out_dir / "kernels_mesh.json"
    spec = {"expect_platform": smoke.expect_platform, "context": context,
            "slots": slots, "interpret": interpret, "seed": 1, "mesh": chips,
            "report": str(report_path), **heads}
    smoke.say(f"kernel phase over {chips} chips: starting")
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--kernel-child",
         json.dumps(spec)], env=smoke.child_env(env), timeout=timeout)
    check(proc.returncode == 0,
          f"kernel phase over {chips} chips: child exited {proc.returncode}")
    report = json.loads(report_path.read_text())
    check(report["device"]["count"] >= chips,
          f"kernel phase over {chips} chips saw {report['device']}")
    for c in report["cases"]:
        smoke.say(f"kernel {c['case']}: max_abs_err={c['max_abs_err']}")
    smoke.report["phases"]["kernels_mesh"] = report
    return report


# ---------------------------------------------------------------------------
# server phase
# ---------------------------------------------------------------------------

def http(method: str, url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


def metric_samples(text: str) -> list[tuple[str, dict, float]]:
    """Prometheus exposition → [(name, labels, value)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for part in rest.rstrip("}").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k] = v.strip('"')
        out.append((name, labels, float(value)))
    return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_models_dir(path: Path, *, model: str, context: int, slots: int,
                     quantization: str | None = "int8",
                     engine: dict | None = None) -> str:
    """The models dir a user would write; everything not named is default."""
    path.mkdir(parents=True, exist_ok=True)
    eng = {"max_slots": slots, **(engine or {})}
    if quantization:
        eng["quantization"] = quantization
    lines = ["name: smoke", f'model: "{model}"', f"context_size: {context}",
             "engine:"] + [f"  {k}: {json.dumps(v)}" for k, v in eng.items()]
    (path / "smoke.yaml").write_text("\n".join(lines) + "\n")
    return "smoke"


class Server:
    """``python -m localai_tpu.cli.main run`` as a child process."""

    def __init__(self, smoke: Smoke, models_dir: Path, name: str, *,
                 tag: str, args=(), env=None):
        self.smoke, self.name, self.tag = smoke, name, tag
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = smoke.out_dir / f"server_{tag}.log"
        self._log = open(self.log_path, "w")
        # the model name as a positional argument = load it before serving
        # (a load that fails ends the process, api.server.serve)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "localai_tpu.cli.main", "run", name,
             "--address", "127.0.0.1", "--port", str(self.port),
             "--models-path", str(models_dir), *args],
            env=smoke.child_env(env), stdout=self._log,
            stderr=subprocess.STDOUT, cwd=str(smoke.out_dir))

    def get(self, path: str, **kw):
        return json.loads(http("GET", self.base + path, **kw))

    def post(self, path: str, body: dict, **kw):
        return json.loads(http("POST", self.base + path, body, **kw))

    def metrics(self) -> list[tuple[str, dict, float]]:
        return metric_samples(http("GET", self.base + "/metrics"))

    def wait_loaded(self, timeout: float) -> float:
        """Until /readyz lists the model in models_loaded — /readyz alone
        answers ok with nothing loaded."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            check(self.proc.poll() is None,
                  f"server exited {self.proc.returncode} before it was "
                  f"ready; see {self.log_path}")
            try:
                ready = self.get("/readyz", timeout=5)
                if self.name in ready["models_loaded"]:
                    return time.monotonic() - t0
            except (urllib.error.URLError, ConnectionError, OSError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"model not loaded after {timeout}s; "
                           f"see {self.log_path}")

    def stop(self) -> None:
        """SIGTERM, a clean exit, and no traceback in the log."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
                raise SmokeFailure("server ignored SIGTERM for 60 s")
        self._log.close()
        log = self.log_path.read_text(errors="replace")
        check(self.proc.returncode == 0,
              f"server exited {self.proc.returncode} on SIGTERM")
        check("Traceback (most recent call last)" not in log,
              f"traceback in {self.log_path}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(10)
        if not self._log.closed:
            self._log.close()


def chat(server: Server, content: str, max_tokens: int, *,
         stream: bool = False) -> dict:
    """One greedy /v1/chat/completions request; returns {text, usage}."""
    body = {"model": server.name, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content": content}],
            "temperature": 0, "ignore_eos": True, "logit_bias": LETTERS}
    if not stream:
        r = server.post("/v1/chat/completions", body)
        choice = r["choices"][0]
        out = {"text": choice["message"]["content"], "usage": r["usage"],
               "finish_reason": choice["finish_reason"]}
    else:
        raw = http("POST", server.base + "/v1/chat/completions",
                   {**body, "stream": True})
        frames = [line[6:] for line in raw.splitlines()
                  if line.startswith("data: ")]
        check(frames and frames[-1] == "[DONE]", "SSE stream not terminated")
        chunks = [json.loads(f) for f in frames[:-1]]
        text = "".join(c["choices"][0]["delta"].get("content") or ""
                       for c in chunks if c["choices"])
        usage = next(c["usage"] for c in reversed(chunks) if c.get("usage"))
        out = {"text": text, "usage": usage, "chunks": len(chunks),
               "finish_reason": next(
                   c["choices"][0]["finish_reason"] for c in reversed(chunks)
                   if c["choices"] and c["choices"][0]["finish_reason"])}
    check(out["usage"]["completion_tokens"] == max_tokens
          and out["finish_reason"] == "length",
          f"asked {max_tokens} tokens, got {out['usage']} "
          f"finish={out['finish_reason']}")
    # every token is one biased ASCII letter
    check(len(out["text"]) == max_tokens and out["text"].isalpha(),
          f"reply {out['text']!r} is not {max_tokens} letters")
    return out


def text_of(n: int, salt: str) -> str:
    """n bytes of non-repeating prose-like text (the byte tokenizer makes it
    n tokens; no bigram repeats early, so the n-gram drafter stays out)."""
    words, i = [], 0
    while sum(len(w) + 1 for w in words) < n:
        words.append(f"{salt}{i:x}")
        i += 7
    return " ".join(words)[:n]


def drive_requests(smoke: Smoke, server: Server, *, slots: int,
                   long_prompt: int, context: int) -> dict:
    """The handful of requests, and what each must show."""
    seen: dict = {}

    def counter(name: str) -> float:
        return sum(v for n, _, v in server.metrics() if n == name)

    # the same greedy prompt twice gives the same tokens (short: below one
    # KV block, so the second run cannot take a different, prefix-sharing
    # prefill path)
    a = chat(server, "determinism", 32)
    b = chat(server, "determinism", 32)
    check(a["text"] == b["text"],
          f"greedy replies differ: {a['text']!r} vs {b['text']!r}")
    seen["short_twice"] = a["text"]
    smoke.say(f"request short x2: identical greedy tokens {a['text']!r}")

    # a prompt longer than the prefill chunk: chunked prefill, two programs
    r = chat(server, text_of(long_prompt, "long"), 16)
    check(r["usage"]["prompt_tokens"] >= long_prompt, f"prompt {r['usage']}")
    smoke.say(f"request long prompt: {r['usage']['prompt_tokens']} prompt "
              f"tokens in chunks, 16 generated")

    # one SSE stream
    r = chat(server, "stream this", 32, stream=True)
    check(r["chunks"] >= 3, f"SSE produced {r['chunks']} frames")
    smoke.say(f"request stream: 32 tokens over {r['chunks']} SSE frames")

    # more requests than slots at once: decode_paged_n over a full batch,
    # continuous batching, a queue
    n = slots + 2
    with concurrent.futures.ThreadPoolExecutor(n) as pool:
        futs = [pool.submit(chat, server, text_of(40 + 9 * i, f"c{i}"), 48)
                for i in range(n)]
        replies = [f.result() for f in futs]
    check(len(replies) == n, "a concurrent request was lost")
    smoke.say(f"requests concurrent: {n} over {slots} slots, 48 tokens each")

    # a repeated prompt: the second admission shares the first's KV blocks
    reused0 = counter("localai_prefix_tokens_reused_total")
    shared = text_of(min(300, context // 3), "shared")
    chat(server, shared, 8)
    chat(server, shared, 8)
    reused = counter("localai_prefix_tokens_reused_total") - reused0
    check(reused >= 64, f"repeated prompt reused {reused} prefix tokens")
    smoke.say(f"request repeated prompt: {reused:.0f} prefix tokens reused")

    # a repetitive prompt: the default-on n-gram speculation lane
    windows0 = counter("localai_speculative_windows_total")
    chat(server, "abcdefg " * 8, 48)
    windows = counter("localai_speculative_windows_total") - windows0
    check(windows >= 1, "no speculative window on a repetitive prompt")
    smoke.say(f"request repetitive prompt: {windows:.0f} speculative "
              f"verify windows")
    seen.update(prefix_tokens_reused=reused, speculative_windows=windows)
    return seen


def check_devices(smoke: Smoke, system: dict, chips: int) -> None:
    devices = system["devices"]
    check(len(devices) == chips,
          f"/system lists {len(devices)} devices, expected {chips}")
    for d in devices:
        check(d["platform"] == smoke.expect_platform,
              f"/system device {d} is not {smoke.expect_platform}")
        if smoke.device:
            check(d["kind"] == smoke.device["kind"],
                  f"/system device kind {d['kind']!r} != kernel phase's "
                  f"{smoke.device['kind']!r}")
    smoke.device = {"platform": devices[0]["platform"],
                    "kind": devices[0]["kind"], "count": len(devices)}


def check_memory(smoke: Smoke, server: Server, *, label: str, chips: int,
                 peak_headroom: float | None = None) -> dict:
    """/debug/devices against the census: what the allocator holds is the
    weights plus the pool, spread evenly, and its peak stayed under the
    limit — or, with ``peak_headroom``, within that many bytes of the
    chip's share."""
    dbg = server.get("/debug/devices")
    check(dbg["probe"]["ok"], f"device probe failed: {dbg['probe']}")
    cats = dbg["census"]["by_category"]
    served = cats["weights"] + cats["kv_cache"]
    out = {"census": cats, "devices": dbg["devices"]}
    if smoke.expect_platform != "tpu":
        return out                    # the CPU backend has no allocator stats
    share = served / chips
    for d in dbg["devices"]:
        mem = d["memory"]
        check(mem is not None, f"device {d['id']} reports no memory stats")
        use, peak, limit = (mem["bytes_in_use"], mem["peak_bytes_in_use"],
                            mem["bytes_limit"])
        smoke.say(f"memory {label} device {d['id']}: in_use="
                  f"{use / 2**30:.2f} GiB peak={peak / 2**30:.2f} GiB "
                  f"limit={limit / 2**30:.2f} GiB (weights+pool share "
                  f"{share / 2**30:.2f} GiB)")
        # in use = this chip's share of weights + pool, plus decode state,
        # rope tables and results in flight (well under 1 GiB); on four
        # chips "about a quarter each", not everything on chip 0
        check(0.85 * share <= use <= share + 2**30,
              f"device {d['id']} holds {use} bytes, expected about {share}")
        check(peak < limit, f"device {d['id']} peak {peak} hit its limit")
        if peak_headroom is not None:
            check(peak <= share + peak_headroom,
                  f"device {d['id']} peaked at {peak} bytes: more than its "
                  f"share of the served form ({share:.0f}) plus one leaf")
    return out


def server_phase(smoke: Smoke, *, chips: int = 1,
                 model: str = "debug:llama3-8b", context: int = CONTEXT,
                 slots: int = SLOTS, quantization: str | None = "int8",
                 long_prompt: int = 700, expect_impl: str = "pallas",
                 engine: dict | None = None, load_timeout: float = 600.0,
                 tag: str = "") -> dict:
    """Serve the model in one process over ``chips`` chips and assert from
    outside. chips == 1 pins the server to chip 0; more chips are left to
    the manager's own default (the auto mesh)."""
    tag = tag or f"{chips}chip"
    models_dir = smoke.out_dir / f"models_{tag}"
    name = write_models_dir(models_dir, model=model, context=context,
                            slots=slots, quantization=quantization,
                            engine=engine)
    smoke.say(f"server phase {tag}: starting {model} "
              f"quantization={quantization} slots={slots} context={context}")
    server = Server(smoke, models_dir, name, tag=tag,
                    env=smoke.one_chip_env() if chips == 1 else None)
    try:
        load_s = server.wait_loaded(load_timeout)
        check_devices(smoke, server.get("/system"), chips)
        smoke.say(f"server phase {tag}: model loaded and serving after "
                  f"{load_s:.0f}s")
        phase: dict = {"load_seconds": round(load_s, 1)}
        # peak right after load: the bf16 model (2x the int8 one) or an f32
        # copy of a leaf (up to 7.5 GB) would show here
        phase["memory_after_load"] = check_memory(
            smoke, server, label="after load", chips=chips,
            peak_headroom=1.5 * 2**30)
        phase["requests"] = drive_requests(
            smoke, server, slots=slots, long_prompt=long_prompt,
            context=context)
        phase["memory_after_requests"] = check_memory(
            smoke, server, label="after requests", chips=chips)

        samples = server.metrics()

        def value(name, **labels):
            return [v for n, lab, v in samples if n == name
                    and all(lab.get(k) == w for k, w in labels.items())]

        check(value("localai_paged_kernel_impl", impl=expect_impl) == [1.0],
              f"paged kernel impl is not {expect_impl}: "
              f"{[s for s in samples if s[0] == 'localai_paged_kernel_impl']}")
        # who writes a decode step's rows: the kernel wherever it serves
        # (the smoke's pools are bf16), the scatter under gather + XLA
        writer = "scatter" if expect_impl == "lax" else "kernel"
        check(value("localai_paged_kv_write_impl", impl=writer) == [1.0],
              f"a decode step's K/V rows are not written by the {writer}: "
              f"{[s for s in samples if s[0] == 'localai_paged_kv_write_impl']}")
        check(not any(value("localai_engine_rebuilds_total")),
              "the engine was rebuilt")
        check(not any(value("localai_stalls_total")), "a stall was recorded")
        check(not any(value("localai_engine_stalled")), "a channel is stalled")
        check(not any(value("localai_engine_failed")), "the engine failed")
        check(not any(value("localai_nan_rows_total")), "non-finite logits")
        phase["compile_seconds"] = {
            lab["program"]: round(v, 1) for n, lab, v in samples
            if n == "localai_xla_compile_seconds_total"
            and not lab["program"].startswith("/")}
        phase["compile_count"] = {
            lab["program"]: int(v) for n, lab, v in samples
            if n == "localai_xla_compile_total"
            and not lab["program"].startswith("/")}
        smoke.say(f"server phase {tag}: kernel impl {expect_impl}, no "
                  f"rebuilds, no stalls; first-dispatch seconds by program "
                  f"{phase['compile_seconds']}")
        # per-program HBM as the compiler accounts it (re-lowered from the
        # recorded signatures; the shared compilation cache makes it cheap)
        phase["programs"] = [
            {k: p.get(k) for k in (
                "program", "statics", "first_dispatch_seconds", "dispatches",
                "argument_bytes", "temp_bytes", "output_bytes", "cost_error")}
            for p in server.get("/debug/programs")["programs"]]
    except BaseException:
        server.kill()
        raise
    server.stop()
    smoke.say(f"server phase {tag}: clean exit on SIGTERM, no traceback")
    smoke.report["phases"][f"server_{tag}"] = phase
    return phase


def fleet_phase(smoke: Smoke, *, replicas: int,
                model: str = "debug:llama3-8b", context: int = CONTEXT,
                slots: int = SLOTS, quantization: str | None = "int8",
                expect_impl: str = "pallas", engine: dict | None = None,
                load_timeout: float = 900.0) -> dict:
    """``replicas`` pinned one-chip workers behind the fleet router. The
    server itself runs on the CPU (--platform cpu): a chip belongs to one
    process, and the workers need all of them."""
    tag = f"fleet{replicas}"
    models_dir = smoke.out_dir / f"models_{tag}"
    name = write_models_dir(models_dir, model=model, context=context,
                            slots=slots, quantization=quantization,
                            engine=engine)
    smoke.say(f"fleet phase: {replicas} pinned one-chip workers of {model}")
    server = Server(
        smoke, models_dir, name, tag=tag,
        args=["--platform", "cpu", "--fleet-replicas", str(replicas),
              "--fleet-device-pinning", "--fleet-rpc-timeout-s", "600"],
        # the server's own JAX stays off the chips; its workers must NOT
        # inherit that (fleet.pinning sets their platform explicitly)
        env={"JAX_PLATFORMS": "cpu",
             "LOCALAI_FLEET_PIN_PLATFORM": smoke.expect_platform,
             "LOCALAI_FLEET_PIN_DEVICES": str(replicas)})
    try:
        load_s = server.wait_loaded(load_timeout)
        members = server.get("/v1/fleet")["models"][name]["replicas"]
        check(len(members) == replicas, f"{len(members)} replicas")
        for m in members:
            dev = m.get("device") or {}
            check(dev.get("platform") == smoke.expect_platform
                  and m["state"] == "healthy"
                  and m["engine"]["paged_attn_impl"] == expect_impl,
                  f"replica {m['id']} is {m['state']} on {dev} serving "
                  f"{m.get('engine')}")
            smoke.say(f"fleet replica {m['id']}: platform="
                      f"{dev['platform']} kind={dev['device_kind']} "
                      f"count={dev['device_count']}")
            if smoke.expect_platform == "tpu":
                check(dev["device_count"] == 1,
                      f"replica {m['id']} sees {dev['device_count']} chips")
        # enough concurrent requests that every replica serves some
        # (prompts under one KV block route least-loaded, not by prefix
        # affinity, so a burst spreads evenly)
        n = replicas * 3
        with concurrent.futures.ThreadPoolExecutor(n) as pool:
            futs = [pool.submit(chat, server, text_of(24 + i, f"f{i}"), 32)
                    for i in range(n)]
            for f in futs:
                f.result()
        after = server.get("/v1/fleet")["models"][name]["replicas"]
        served = {m["id"]: m["dispatched"] for m in after}
        check(all(v > 0 for v in served.values()),
              f"a replica served nothing: {served}")
        check(all(m["state"] == "healthy" and m["errors"] == 0
                  for m in after), f"replica errors: {after}")
        smoke.say(f"fleet phase: {n} requests served, per replica {served}")
        phase = {"load_seconds": round(load_s, 1), "served": served,
                 "replicas": [{"id": m["id"], "device": m["device"],
                               "paged_attn_impl":
                                   m["engine"]["paged_attn_impl"]}
                              for m in members]}
    except BaseException:
        server.kill()
        raise
    server.stop()
    smoke.say("fleet phase: clean exit on SIGTERM, no traceback")
    smoke.report["phases"][tag] = phase
    return phase


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: kernel phase + the one-chip server, pinned to "
                         "chip 0 (default). 4: kernel phase + the meshed "
                         "server over four chips + four pinned one-chip "
                         "workers behind the fleet router")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="report, server logs, compilation cache")
    ap.add_argument("--kernel-child", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.kernel_child:
        return kernel_child(json.loads(args.kernel_child))

    smoke = Smoke(Path(args.out))
    t0 = time.monotonic()
    try:
        kernel_phase(smoke)
        if args.chips > 1:
            mesh_kernel_phase(smoke, chips=args.chips)
        server_phase(smoke, chips=args.chips)
        # the looped decoder through the same entry points (one chip, pinned:
        # its two heads split over no mesh): passes x layers of cache, the
        # compiled kernel at one query row a kv head
        server_phase(smoke, chips=1, **LOOPED_SERVER)
        if args.chips > 1:
            fleet_phase(smoke, replicas=args.chips)
    finally:
        smoke.close()
    smoke.report.update(device=smoke.device, chips=args.chips,
                        seconds=round(time.monotonic() - t0, 1), claim=None)
    smoke.save_report()
    smoke.say(f"all phases passed in {smoke.report['seconds']}s; report in "
              f"{smoke.out_dir / 'report.json'}")
    smoke.say("summary " + json.dumps(
        {"chips": args.chips, "phases": sorted(smoke.report["phases"]),
         "claim": None}))
    # the result line: these keys and no others (the driver's contract)
    print(json.dumps({"ok": True, "device": {
        "platform": smoke.device["platform"], "kind": smoke.device["kind"],
        "count": smoke.device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
