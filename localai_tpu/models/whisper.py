"""Whisper-family speech-to-text, pure functional JAX.

TPU-era replacement for the whisper.cpp cgo backend
(/root/reference/backend/go/transcribe/whisper/whisper.go:21-105): same
capability — full-file transcription with segments behind the
AudioTranscription RPC — but as an encoder-decoder transformer running
under jit, fed by the on-device log-mel frontend (audio.mel).

Structure mirrors models.llama: stacked per-layer params scanned with
``lax.scan``, static shapes, f32 norms. The decoder uses a fixed-size
token buffer with length masking so greedy decoding reuses ONE compiled
program for every step (no per-length recompiles).
"""

from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from localai_tpu.audio import mel as melmod

PyTree = Any


@dataclasses.dataclass(frozen=True)
class WhisperConfig:
    n_mels: int = 80
    d_model: int = 384            # whisper-tiny
    n_heads: int = 6
    n_enc_layers: int = 4
    n_dec_layers: int = 4
    d_ff: int = 1536
    vocab_size: int = 51865
    max_source_positions: int = 1500   # CHUNK_FRAMES // 2
    max_target_positions: int = 448
    # special token ids (whisper multilingual defaults)
    sot: int = 50258
    eot: int = 50257
    token_transcribe: int = 50359
    token_translate: int = 50358
    token_notimestamps: int = 50363
    lang_base: int = 50259             # <|en|>
    dtype: str = "float32"

    @property
    def hd(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def from_hf(cls, hf: dict) -> "WhisperConfig":
        return cls(
            n_mels=hf.get("num_mel_bins", 80),
            d_model=hf.get("d_model", 384),
            n_heads=hf.get("encoder_attention_heads", 6),
            n_enc_layers=hf.get("encoder_layers", 4),
            n_dec_layers=hf.get("decoder_layers", 4),
            d_ff=hf.get("encoder_ffn_dim", 1536),
            vocab_size=hf.get("vocab_size", 51865),
            max_source_positions=hf.get("max_source_positions", 1500),
            max_target_positions=hf.get("max_target_positions", 448),
            sot=hf.get("decoder_start_token_id", 50258),
            eot=hf.get("eos_token_id", 50257),
        )


# whisper's language order — token id = lang_base + index
LANGUAGES = (
    "en zh de es ru ko fr ja pt tr pl ca nl ar sv it id hi fi vi he uk el ms "
    "cs ro da hu ta no th ur hr bg lt la mi ml cy sk te fa lv bn sr az sl kn "
    "et mk br eu is hy ne mn bs kk sq sw gl mr pa si km sn yo so af oc ka be "
    "tg sd gu am yi lo uz fo ht ps tk nn mt sa lb my bo tl mg as tt haw ln "
    "ha ba jw su"
).split()


def language_token(cfg: "WhisperConfig", language: Optional[str]) -> int:
    """language code/name → <|xx|> token id (defaults to English)."""
    if not language:
        return cfg.lang_base
    code = language.strip().lower()
    aliases = {"english": "en", "french": "fr", "german": "de",
               "spanish": "es", "chinese": "zh", "japanese": "ja",
               "korean": "ko", "russian": "ru", "portuguese": "pt",
               "italian": "it", "dutch": "nl", "arabic": "ar",
               "hindi": "hi", "turkish": "tr", "polish": "pl"}
    code = aliases.get(code, code)
    try:
        return cfg.lang_base + LANGUAGES.index(code)
    except ValueError:
        return cfg.lang_base


DEBUG_CONFIG = WhisperConfig(
    d_model=64, n_heads=4, n_enc_layers=2, n_dec_layers=2, d_ff=128,
    vocab_size=512, max_source_positions=1500, max_target_positions=64,
    sot=500, eot=501, token_transcribe=502, token_translate=503,
    token_notimestamps=504, lang_base=505,
)


def _attn_block_shapes(d: int) -> dict:
    return {
        "ln": (d,), "ln_b": (d,),
        "wq": (d, d), "bq": (d,),
        "wk": (d, d),
        "wv": (d, d), "bv": (d,),
        "wo": (d, d), "bo": (d,),
    }


def param_shapes(cfg: WhisperConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    Le, Ld = cfg.n_enc_layers, cfg.n_dec_layers

    def stack(shapes: dict, n: int) -> dict:
        return {k: (n, *v) for k, v in shapes.items()}

    mlp = {"ln2": (d,), "ln2_b": (d,), "fc1": (d, f), "b1": (f,),
           "fc2": (f, d), "b2": (d,)}
    enc_layer = {**{f"sa_{k}": v for k, v in _attn_block_shapes(d).items()},
                 **mlp}
    dec_layer = {**{f"sa_{k}": v for k, v in _attn_block_shapes(d).items()},
                 **{f"ca_{k}": v for k, v in _attn_block_shapes(d).items()},
                 **mlp}
    return {
        "conv1_w": (d, cfg.n_mels, 3), "conv1_b": (d,),
        "conv2_w": (d, d, 3), "conv2_b": (d,),
        "enc": stack(enc_layer, Le),
        "enc_ln": (d,), "enc_ln_b": (d,),
        "embed": (cfg.vocab_size, d),
        "pos": (cfg.max_target_positions, d),
        "dec": stack(dec_layer, Ld),
        "dec_ln": (d,), "dec_ln_b": (d,),
    }


_GAIN_NAMES = {"sa_ln", "ca_ln", "ln2", "enc_ln", "dec_ln"}


def init_params(rng: jax.Array, cfg: WhisperConfig) -> PyTree:
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(flat))

    def mk(k, shape):
        if len(shape) == 1:
            return jnp.zeros(shape, jnp.float32)  # biases; gains fixed below
        return jax.random.normal(k, shape, jnp.float32) * 0.02

    out = jax.tree.unflatten(treedef, [mk(k, s) for k, s in zip(keys, flat)])

    def fix(path, leaf):
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if name in _GAIN_NAMES:
            return jnp.ones_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, out)


def _ln(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _mha(cfg: WhisperConfig, q_in, kv_in, p, prefix, mask=None):
    """q_in [Tq, D], kv_in [Tk, D] → [Tq, D]. Whisper has no k bias."""
    H, hd = cfg.n_heads, cfg.hd
    q = (q_in @ p[f"{prefix}_wq"] + p[f"{prefix}_bq"]).reshape(-1, H, hd)
    k = (kv_in @ p[f"{prefix}_wk"]).reshape(-1, H, hd)
    v = (kv_in @ p[f"{prefix}_wv"] + p[f"{prefix}_bv"]).reshape(-1, H, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask[None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(-1, cfg.d_model)
    return out @ p[f"{prefix}_wo"] + p[f"{prefix}_bo"]


def _sinusoids(length: int, channels: int) -> jax.Array:
    """Whisper's sinusoidal encoder positions."""
    log_timescale = math.log(10000) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2))
    scaled = jnp.arange(length)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


def encode(cfg: WhisperConfig, params: PyTree, mel: jax.Array) -> jax.Array:
    """mel [n_mels, frames] → encoder states [frames//2, D]."""
    x = mel.T[None]  # [1, frames, n_mels]
    # explicit (1, 1) padding, NOT "SAME": at conv2's stride 2, XLA SAME
    # resolves to (0, 1) while the reference torch Conv1d(padding=1) pads
    # both sides — SAME silently shifted every frame by one input step
    # (caught by tests/test_llama_torch.py::test_whisper_matches_torch)
    x = jax.nn.gelu(
        lax.conv_general_dilated(
            x, params["conv1_w"].transpose(2, 1, 0), (1,), ((1, 1),),
            dimension_numbers=("NWC", "WIO", "NWC"),
        ) + params["conv1_b"]
    )
    x = jax.nn.gelu(
        lax.conv_general_dilated(
            x, params["conv2_w"].transpose(2, 1, 0), (2,), ((1, 1),),
            dimension_numbers=("NWC", "WIO", "NWC"),
        ) + params["conv2_b"]
    )
    x = x[0]  # [T', D]
    x = x + _sinusoids(x.shape[0], cfg.d_model)

    def body(carry, lp):
        h = carry
        a = _mha(cfg, _ln(h, lp["sa_ln"], lp["sa_ln_b"]),
                 _ln(h, lp["sa_ln"], lp["sa_ln_b"]), lp, "sa")
        h = h + a
        m = _ln(h, lp["ln2"], lp["ln2_b"])
        h = h + (jax.nn.gelu(m @ lp["fc1"] + lp["b1"]) @ lp["fc2"] + lp["b2"])
        return h, None

    x, _ = lax.scan(body, x, params["enc"])
    return _ln(x, params["enc_ln"], params["enc_ln_b"])


def decode_logits(cfg: WhisperConfig, params: PyTree, tokens: jax.Array,
                  length: jax.Array, enc: jax.Array) -> jax.Array:
    """tokens [Tmax] (padded), length scalar → logits [V] at length-1."""
    Tmax = tokens.shape[0]
    x = params["embed"][tokens] + params["pos"][:Tmax]
    t = jnp.arange(Tmax)
    causal = (t[:, None] >= t[None, :]) & (t[None, :] < length)

    def body(carry, lp):
        h = carry
        a = _mha(cfg, _ln(h, lp["sa_ln"], lp["sa_ln_b"]),
                 _ln(h, lp["sa_ln"], lp["sa_ln_b"]), lp, "sa", mask=causal)
        h = h + a
        c = _mha(cfg, _ln(h, lp["ca_ln"], lp["ca_ln_b"]), enc, lp, "ca")
        h = h + c
        m = _ln(h, lp["ln2"], lp["ln2_b"])
        h = h + (jax.nn.gelu(m @ lp["fc1"] + lp["b1"]) @ lp["fc2"] + lp["b2"])
        return h, None

    x, _ = lax.scan(body, x, params["dec"])
    x = _ln(x, params["dec_ln"], params["dec_ln_b"])
    last = jax.lax.dynamic_index_in_dim(x, length - 1, keepdims=False)
    return last @ params["embed"].T


def _cached_step(cfg: WhisperConfig, params: PyTree, token, t,
                 sa_k, sa_v, ca_k, ca_v):
    """One KV-cached decoder step at position ``t``.

    sa_k/sa_v [L, Tmax, D] — projected self-attn keys/values per layer;
    ca_k/ca_v [L, Tenc, D] — cross-attn projections precomputed once per
    chunk (the encoder output is fixed). Returns (logits [V], sa_k, sa_v).
    """
    H, hd, D = cfg.n_heads, cfg.hd, cfg.d_model
    Tmax = sa_k.shape[1]
    x = params["embed"][token] + params["pos"][t]          # [D]
    idx = jnp.arange(Tmax)

    def layer(x, inputs):
        lp, sak_l, sav_l, cak_l, cav_l = inputs
        h = _ln(x, lp["sa_ln"], lp["sa_ln_b"])
        q = (h @ lp["sa_wq"] + lp["sa_bq"]).reshape(H, hd)
        k_t = h @ lp["sa_wk"]
        v_t = h @ lp["sa_wv"] + lp["sa_bv"]
        keys = sak_l.at[t].set(k_t).reshape(Tmax, H, hd)
        vals = sav_l.at[t].set(v_t).reshape(Tmax, H, hd)
        s = jnp.einsum("hd,khd->hk", q, keys) / math.sqrt(hd)
        s = jnp.where(idx[None, :] <= t, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
        a = jnp.einsum("hk,khd->hd", p, vals).reshape(D)
        x = x + a @ lp["sa_wo"] + lp["sa_bo"]

        h2 = _ln(x, lp["ca_ln"], lp["ca_ln_b"])
        q2 = (h2 @ lp["ca_wq"] + lp["ca_bq"]).reshape(H, hd)
        kk = cak_l.reshape(-1, H, hd)
        vv = cav_l.reshape(-1, H, hd)
        s2 = jnp.einsum("hd,khd->hk", q2, kk) / math.sqrt(hd)
        p2 = jax.nn.softmax(s2.astype(jnp.float32), axis=-1).astype(vv.dtype)
        c = jnp.einsum("hk,khd->hd", p2, vv).reshape(D)
        x = x + c @ lp["ca_wo"] + lp["ca_bo"]

        m = _ln(x, lp["ln2"], lp["ln2_b"])
        x = x + (jax.nn.gelu(m @ lp["fc1"] + lp["b1"]) @ lp["fc2"]
                 + lp["b2"])
        return x, (k_t, v_t)

    x, (krows, vrows) = lax.scan(
        layer, x, (params["dec"], sa_k, sa_v, ca_k, ca_v))
    sa_k = sa_k.at[:, t].set(krows)
    sa_v = sa_v.at[:, t].set(vrows)
    x = _ln(x, params["dec_ln"], params["dec_ln_b"])
    return x @ params["embed"].T, sa_k, sa_v


def decode_greedy(cfg: WhisperConfig, params: PyTree, prompt_buf, n_prompt,
                  enc, limit):
    """Whole-chunk greedy decode as ONE program: prompt prefill + generate
    until <eot>, KV-cached (self-attn cache + cross-attn K/V precompute).

    The per-token host loop this replaces re-ran the FULL decoder over the
    padded buffer every step — O(T²) compute per token and one dispatch
    per token. Returns (buf [Tmax], n_total) with generated
    ids at buf[n_prompt:n_total] (eot excluded)."""
    Ld, D = cfg.n_dec_layers, cfg.d_model
    Tmax = cfg.max_target_positions
    ca_k = jnp.einsum("td,lde->lte", enc, params["dec"]["ca_wk"])
    ca_v = (jnp.einsum("td,lde->lte", enc, params["dec"]["ca_wv"])
            + params["dec"]["ca_bv"][:, None])
    sa_k = jnp.zeros((Ld, Tmax, D), enc.dtype)
    sa_v = jnp.zeros((Ld, Tmax, D), enc.dtype)

    def cond(c):
        t, buf, sak, sav, done, n_gen = c
        return (~done) & (n_gen < limit) & (t < Tmax - 1)

    def body(c):
        t, buf, sak, sav, done, n_gen = c
        logits, sak, sav = _cached_step(
            cfg, params, buf[t], t, sak, sav, ca_k, ca_v)
        nxt = jnp.argmax(logits).astype(jnp.int32)
        is_gen = t + 1 >= n_prompt
        write = is_gen & (nxt != cfg.eot)
        buf = jnp.where(write, buf.at[t + 1].set(nxt), buf)
        done = is_gen & (nxt == cfg.eot)
        return t + 1, buf, sak, sav, done, n_gen + write.astype(jnp.int32)

    _, buf, _, _, _, n_gen = lax.while_loop(
        cond, body, (jnp.int32(0), prompt_buf, sa_k, sa_v,
                     jnp.bool_(False), jnp.int32(0)))
    return buf, n_prompt + n_gen


class WhisperModel:
    """Loaded whisper engine: jitted encode + ONE-dispatch KV-cached
    greedy decode per chunk (decode_greedy)."""

    def __init__(self, cfg: WhisperConfig, params: PyTree, tokenizer=None):
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.filters = jnp.asarray(melmod.mel_filterbank(cfg.n_mels))
        self._encode = jax.jit(lambda p, m: encode(cfg, p, m))
        self._greedy = jax.jit(
            lambda p, buf, n, enc, lim: decode_greedy(
                cfg, p, buf, n, enc, lim)
        )

    def transcribe_chunk(self, audio: np.ndarray, *,
                         language: Optional[str] = None,
                         translate: bool = False,
                         max_tokens: Optional[int] = None) -> list[int]:
        """One ≤30-s chunk → decoded token ids (specials stripped)."""
        cfg = self.cfg
        mel = melmod.log_mel(jnp.asarray(audio), self.filters,
                             n_mels=cfg.n_mels)
        enc = self._encode(self.params, mel)
        task = cfg.token_translate if translate else cfg.token_transcribe
        prompt = [cfg.sot, language_token(cfg, language), task,
                  cfg.token_notimestamps]
        buf = np.zeros(cfg.max_target_positions, np.int32)
        buf[:len(prompt)] = prompt
        limit = min(max_tokens or cfg.max_target_positions,
                    cfg.max_target_positions - len(prompt))
        out_buf, n_total = self._greedy(
            self.params, jnp.asarray(buf), jnp.int32(len(prompt)), enc,
            jnp.int32(limit),
        )
        ids = np.asarray(out_buf)[len(prompt): int(n_total)]
        return [int(t) for t in ids if t < cfg.eot and t < cfg.sot]

    def transcribe(self, audio: np.ndarray, *,
                   language: Optional[str] = None,
                   translate: bool = False,
                   max_tokens_per_chunk: Optional[int] = None) -> dict:
        """Full-file transcription → {text, segments} (parity: the segment
        schema of whisper.go:28-105 / schema.TranscriptionResult)."""
        segments = []
        texts = []
        for i, chunk in enumerate(melmod.chunk_audio(audio)):
            ids = self.transcribe_chunk(
                chunk, language=language, translate=translate,
                max_tokens=max_tokens_per_chunk,
            )
            text = self._decode_text(ids)
            start = i * melmod.CHUNK_SECONDS
            end = min((i + 1) * melmod.CHUNK_SECONDS,
                      max(len(audio), 1) / melmod.SAMPLE_RATE)
            segments.append({
                "id": i,
                "start": float(start),
                "end": float(end),
                "text": text,
                "tokens": ids,
            })
            texts.append(text)
        return {"text": " ".join(t for t in texts if t).strip(),
                "segments": segments}

    def _decode_text(self, ids: list[int]) -> str:
        if self.tokenizer is not None:
            return self.tokenizer.decode(ids)
        return bytes(i for i in ids if i < 256).decode("utf-8", "replace")


def debug_model(seed: int = 0) -> WhisperModel:
    cfg = DEBUG_CONFIG
    return WhisperModel(cfg, init_params(jax.random.key(seed), cfg))


# HF transformers WhisperForConditionalGeneration → stacked layout
_HF_ENC = "model.encoder.layers.{i}."
_HF_DEC = "model.decoder.layers.{i}."


def _map_attn(get, hf_prefix: str, ours_prefix: str, i: int, out: dict):
    hp = hf_prefix.format(i=i)
    out[f"{ours_prefix}_wq"].append(get(hp + "q_proj.weight").T)
    out[f"{ours_prefix}_bq"].append(get(hp + "q_proj.bias"))
    out[f"{ours_prefix}_wk"].append(get(hp + "k_proj.weight").T)
    out[f"{ours_prefix}_wv"].append(get(hp + "v_proj.weight").T)
    out[f"{ours_prefix}_bv"].append(get(hp + "v_proj.bias"))
    out[f"{ours_prefix}_wo"].append(get(hp + "out_proj.weight").T)
    out[f"{ours_prefix}_bo"].append(get(hp + "out_proj.bias"))


def load_hf_whisper(model_dir: str | Path) -> WhisperModel:
    """Load a HF whisper checkpoint (config.json + model.safetensors)."""
    import json

    from safetensors import safe_open

    model_dir = Path(model_dir)
    hf_cfg = json.loads((model_dir / "config.json").read_text())
    cfg = WhisperConfig.from_hf(hf_cfg)
    f = safe_open(str(model_dir / "model.safetensors"), framework="np")

    def get(name: str) -> np.ndarray:
        return np.asarray(f.get_tensor(name), np.float32)

    def stack_layers(hf_prefix: str, n: int, cross: bool) -> dict:
        acc: dict[str, list] = {}
        keys = ["sa_ln", "sa_ln_b", "sa_wq", "sa_bq", "sa_wk", "sa_wv",
                "sa_bv", "sa_wo", "sa_bo", "ln2", "ln2_b", "fc1", "b1",
                "fc2", "b2"]
        if cross:
            keys += ["ca_ln", "ca_ln_b", "ca_wq", "ca_bq", "ca_wk", "ca_wv",
                     "ca_bv", "ca_wo", "ca_bo"]
        for k in keys:
            acc[k] = []
        for i in range(n):
            hp = hf_prefix.format(i=i)
            acc["sa_ln"].append(get(hp + "self_attn_layer_norm.weight"))
            acc["sa_ln_b"].append(get(hp + "self_attn_layer_norm.bias"))
            _map_attn(get, hf_prefix + "self_attn.", "sa", i, acc)
            if cross:
                acc["ca_ln"].append(
                    get(hp + "encoder_attn_layer_norm.weight"))
                acc["ca_ln_b"].append(
                    get(hp + "encoder_attn_layer_norm.bias"))
                _map_attn(get, hf_prefix + "encoder_attn.", "ca", i, acc)
            acc["ln2"].append(get(hp + "final_layer_norm.weight"))
            acc["ln2_b"].append(get(hp + "final_layer_norm.bias"))
            acc["fc1"].append(get(hp + "fc1.weight").T)
            acc["b1"].append(get(hp + "fc1.bias"))
            acc["fc2"].append(get(hp + "fc2.weight").T)
            acc["b2"].append(get(hp + "fc2.bias"))
        return {k: jnp.asarray(np.stack(v)) for k, v in acc.items()}

    params = {
        "conv1_w": jnp.asarray(get("model.encoder.conv1.weight")),
        "conv1_b": jnp.asarray(get("model.encoder.conv1.bias")),
        "conv2_w": jnp.asarray(get("model.encoder.conv2.weight")),
        "conv2_b": jnp.asarray(get("model.encoder.conv2.bias")),
        "enc": stack_layers(_HF_ENC, cfg.n_enc_layers, cross=False),
        "enc_ln": jnp.asarray(get("model.encoder.layer_norm.weight")),
        "enc_ln_b": jnp.asarray(get("model.encoder.layer_norm.bias")),
        "embed": jnp.asarray(get("model.decoder.embed_tokens.weight")),
        "pos": jnp.asarray(get("model.decoder.embed_positions.weight")),
        "dec": stack_layers(_HF_DEC, cfg.n_dec_layers, cross=True),
        "dec_ln": jnp.asarray(get("model.decoder.layer_norm.weight")),
        "dec_ln_b": jnp.asarray(get("model.decoder.layer_norm.bias")),
    }
    from localai_tpu.utils.tokenizer import load_tokenizer

    return WhisperModel(cfg, params, tokenizer=load_tokenizer(model_dir))
