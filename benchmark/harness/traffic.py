"""The one traffic generator: a mix file's parameters + a seed -> requests.

Everything a run sends is a pure function of (mix file, rate or client count,
seconds, seed). Steadiness comes from drawing a FIXED amount of work: the
inter-arrival gaps, prompt lengths and output lengths of a window are the
quantiles of their distributions at (i + 1/2)/n, shuffled by the seed, so
every seed offers the same multiset of gaps and lengths (the same tokens in
and out, the same number of arrivals) and differs only in order and pairing.

Prompts are seeded random printable bytes (the served models use the byte
tokenizer: one byte, one token), so no two requests share a prefix unless the
mix's ``prefix`` block says so. Warm-up traffic draws from a stream disjoint
from the window's (``stream="warm"``), so the window starts with a prefix cache
that has never seen its prompts.

A mix file (benchmark/traffic/<name>.json) — unknown keys are an error:

  who       who sends such traffic (prose, for PERF.md)
  loop      "open": arrivals on a schedule whether or not earlier requests
            finished (independent users; the cell file gives ``rate_rps``);
            "closed": each of the cell file's ``clients`` sends its next
            request when the previous one completed (callers that wait)
  arrival   {"cv": c}: gamma inter-arrival gaps with coefficient of
            variation c (1 = Poisson, >1 = bursts). Open loop only.
  classes   [{weight, prompt_tokens, output_tokens}]: request classes in one
            queue; each length is {"dist": "lognormal", median, sigma, min,
            max} or {"dist": "fixed", "value": n}
  sampling  request body fields sent verbatim (temperature, top_p, ...)
  prefix    {share, pool, tokens, fill_in_setup}: ``share`` of the requests
            take their first ``tokens`` bytes from one of ``pool`` seeded
            prefixes; fill_in_setup sends each prefix once during set-up
  session   {turns: [lo, hi], think_s: [lo, hi]}: each arrival is a session
            of that many turns; a turn's prompt is the conversation so far
            (replies as served) plus new user text of the class's prompt
            length; the next turn is due think seconds after the reply ended
  shape_seed  an integer: a FIXED REPLAY SET. The mix's shape (which gap
            comes before which request, the lengths and how they pair up,
            turns and think times) is drawn from it and not from --seed, so
            every run replays one schedule and --seed changes only the
            prompts' bytes and the sampling seeds. For a window that holds
            only some tens of requests (long requests, low rates): two
            shuffles of the same work then differ more than two programs do
            (m7b-chat, 32 requests: mean TTFT spread 6.3% shuffled, 1.0%
            replayed; PERF.md section 6, PR 23). Left out, the shape is drawn
            from --seed: right where a window holds thousands of samples.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import numpy as np
from scipy import stats

MIX_KEYS = {"who", "loop", "arrival", "classes", "sampling", "prefix",
            "session", "shape_seed"}
DEFAULTS = {
    "arrival": {"cv": 1.0},
    "sampling": {},
    "prefix": {"share": 0.0, "pool": 0, "tokens": 0, "fill_in_setup": False},
    "session": {"turns": [1, 1], "think_s": [0.0, 0.0]},
}
STREAMS = {"warm": 1, "ramp": 2, "window": 3, "probe": 4, "prefix": 5}

# every generated token is one of the 26 lowercase letters (chip_smoke.py's
# trick): one visible byte per token, so a reply's length in characters is
# its length in tokens and "exactly max_tokens tokens" is read off the text
LETTERS = {str(i): 100.0 for i in range(ord("a"), ord("z") + 1)}
PRINTABLE = (32, 127)


def validate(mix: dict, where: str) -> dict:
    from harness.spec import SpecError

    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise SpecError(f"{where}: unknown keys {sorted(unknown)}")
    out = dict(mix)
    for key, default in DEFAULTS.items():
        given = mix.get(key, {})
        extra = set(given) - set(default) if default else set()
        if extra:
            raise SpecError(f"{where}: unknown keys in {key}: {sorted(extra)}")
        out[key] = {**default, **given}
    if not isinstance(out.get("shape_seed"), (int, type(None))):
        raise SpecError(f"{where}: shape_seed must be an integer")
    if out.get("loop") not in ("open", "closed"):
        raise SpecError(f"{where}: loop must be 'open' or 'closed'")
    if not out.get("classes"):
        raise SpecError(f"{where}: no classes")
    for c in out["classes"]:
        if set(c) != {"weight", "prompt_tokens", "output_tokens"}:
            raise SpecError(f"{where}: a class has keys {sorted(c)}")
        for d in (c["prompt_tokens"], c["output_tokens"]):
            want = ({"dist", "value"} if d.get("dist") == "fixed" else
                    {"dist", "median", "sigma", "min", "max"})
            if d.get("dist") not in ("fixed", "lognormal") or set(d) != want:
                raise SpecError(f"{where}: bad length distribution {d}")
    return out


@dataclasses.dataclass
class Turn:
    user_tokens: int        # new user text this turn, in bytes = tokens
    max_tokens: int
    think_s: float          # pause before this turn (0 for the first)
    seed: int               # the request's sampling seed
    text: str               # the new user text


@dataclasses.dataclass
class Request:
    """One arrival: a single request, or a session of several turns."""

    idx: int
    stream: str
    due: Optional[float]    # seconds from the schedule's start; None = closed
    turns: list[Turn]


def _quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    if dist["dist"] == "fixed":
        return np.full(len(u), int(dist["value"]), np.int64)
    z = stats.norm.ppf(u)
    v = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _strata(rng: np.random.Generator, n: int,
            ends: bool = False) -> np.ndarray:
    """(i + 1/2)/n for i < n, in seeded order; with ``ends`` the grid runs
    from 0 to 1, so a distribution's smallest and largest values are in."""
    grid = np.linspace(0.0, 1.0, n) if ends else (np.arange(n) + 0.5) / n
    return rng.permutation(grid)


def random_text(rng: np.random.Generator, n: int) -> str:
    return rng.integers(*PRINTABLE, size=n, dtype=np.uint8).tobytes().decode(
        "ascii")


def stream_rng(seed: int, stream: str,
               block: int = 0) -> np.random.Generator:
    """One generator per (seed, stream, block): the streams are disjoint."""
    return np.random.default_rng([int(seed), STREAMS[stream], block])


def arrivals(mix: dict, rate: float, span: float,
             rng: np.random.Generator) -> np.ndarray:
    """round(rate*span) arrival times in [0, span): the gaps are the gamma
    distribution's quantiles in seeded order, scaled to fill the span, so the
    count and the multiset of gaps are the same for every seed."""
    n = int(round(rate * span))
    if n <= 0:
        return np.zeros(0)
    cv = float(mix["arrival"]["cv"])
    shape = 1.0 / (cv * cv) if cv > 0 else np.inf
    gaps = (np.ones(n) if not np.isfinite(shape)
            else stats.gamma.ppf(_strata(rng, n), shape))
    gaps *= span / gaps.sum()
    return np.cumsum(gaps) - 0.5 * gaps[0]


def prefixes(mix: dict) -> list[str]:
    """The mix's shared prefixes: the same for every seed, since a cache that
    outlives requests is part of the deployment, not of one run."""
    p = mix["prefix"]
    rng = stream_rng(0, "prefix")
    return [random_text(rng, int(p["tokens"])) for _ in range(int(p["pool"]))]


def shape_rng(mix: dict, seed: int, stream: str,
              block: int = 0) -> np.random.Generator:
    """The generator of a mix's shape: seeded by the mix's ``shape_seed``
    where it has one, else by the run's seed."""
    fixed = mix.get("shape_seed")
    return stream_rng(seed if fixed is None else fixed, stream, block)


def bodies(mix: dict, n: int, seed: int, stream: str,
           block: int = 0, ends: bool = False) -> list[list[Turn]]:
    """n requests' turns (lengths, texts, sampling seeds), stratified over
    the mix's classes and length distributions."""
    return _draw(mix, n, shape_rng(mix, seed, stream, block),
                 stream_rng(seed, stream, block + (1 << 20)), ends)


def _draw(mix: dict, n: int, rng: np.random.Generator,
          content: np.random.Generator,
          ends: bool = False) -> list[list[Turn]]:
    """``rng`` draws the shape, ``content`` the bytes and sampling seeds."""
    classes = mix["classes"]
    w = np.array([c["weight"] for c in classes], float)
    # largest-remainder split of n over the classes, then a seeded order
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts))[: n - counts.sum()]:
        counts[i] += 1
    lo, hi = mix["session"]["turns"]
    tlo, thi = mix["session"]["think_s"]
    pre = prefixes(mix)
    out: list[list[Turn]] = []
    for c, k in zip(classes, counts):
        n_turns = rng.integers(lo, hi + 1, size=k)
        total = int(n_turns.sum())
        users = _quantiles(c["prompt_tokens"], _strata(rng, total, ends))
        outs = _quantiles(c["output_tokens"], _strata(rng, total, ends))
        seeds = content.integers(1, 2**31 - 1, size=total)
        thinks = rng.uniform(tlo, thi, size=total)
        shared = rng.random(k) < mix["prefix"]["share"]
        which = rng.integers(0, max(1, len(pre)), size=k)
        j = 0
        for r in range(k):
            turns = []
            for t in range(int(n_turns[r])):
                n_user = int(users[j])
                head = ""
                if t == 0 and shared[r] and pre:
                    head = pre[which[r]]
                    n_user = max(n_user, len(head) + 16)
                text = head + random_text(content, n_user - len(head))
                turns.append(Turn(n_user, int(outs[j]),
                                  float(thinks[j]) if t else 0.0,
                                  int(seeds[j]), text))
                j += 1
            out.append(turns)
    order = rng.permutation(len(out))
    return [out[i] for i in order]


def open_schedule(mix: dict, rate: float, ramp_s: float, seconds: float,
                  seed: int) -> list[Request]:
    """The ramp's arrivals in [0, ramp_s) and the window's in [ramp_s,
    ramp_s + seconds): two fixed amounts of work, so the window's is the same
    whatever the ramp's length."""
    out: list[Request] = []
    for stream, start, span in (("ramp", 0.0, ramp_s),
                                ("window", ramp_s, seconds)):
        due = arrivals(mix, rate, span, shape_rng(mix, seed, stream, 1)) + start
        for d, turns in zip(due, bodies(mix, len(due), seed, stream)):
            out.append(Request(len(out), stream, float(d), turns))
    return out


def closed_stream(mix: dict, clients: int, seed: int) -> Iterator[Request]:
    """An endless seeded supply for a closed loop, drawn in stratified blocks
    of 8 requests a client. The first request of client i is cut to
    (i+1)/clients of its output length, so completions are spread over a
    request's lifetime from the start instead of arriving in a burst."""
    idx = 0
    for block in range(1 << 30):
        for turns in bodies(mix, 8 * clients, seed, "window", block):
            if idx < clients:
                t = turns[0]
                turns = [dataclasses.replace(t, max_tokens=max(
                    1, -(-t.max_tokens * (idx + 1) // clients)))] + turns[1:]
            yield Request(idx, "window", None, turns)
            idx += 1


def warm_sample(mix: dict, n: int, seed: int, block: int,
                cap_output: int) -> list[Request]:
    """n single-turn requests of the mix's own prompt lengths (its shortest
    and longest among them: stratified quantiles), outputs cut to
    ``cap_output`` tokens: what touches every prefill shape the window can."""
    out = []
    for turns in bodies(mix, n, seed, "warm", block, ends=True):
        t = turns[0]
        out.append(Request(len(out), "warm", None, [dataclasses.replace(
            t, max_tokens=min(t.max_tokens, cap_output))]))
    return out


def request_body(model: str, messages: list[dict], turn: Turn,
                 sampling: dict) -> dict:
    return {"model": model, "messages": messages, "stream": True,
            "max_tokens": turn.max_tokens, "ignore_eos": True,
            "logit_bias": LETTERS, "seed": turn.seed, **sampling}
