"""Finds a cell's files by the names in BENCHMARK.json.

A later PR adds a configuration, a traffic mix, a cell or a per-layer metric
by adding ``benchmark/configs/<n>.json``, ``benchmark/traffic/<n>.json``,
``benchmark/cells/<n>.json`` or ``benchmark/layers/<n>.py`` and one entry in
BENCHMARK.json; nothing here names a cell, a configuration or a metric.

An ARCHITECTURE is added the same way. Everything the benchmark knows about
one (its forward pass in plain float32, and the operations and bytes that
forward pass needs) is ONE module, ``benchmark/reference/<family>.py``, which
a configuration file names as ``reference.family`` (absent: DEFAULT_FAMILY).
Nothing else under ``harness/`` or ``layers/`` names an architecture, a model
type or a layer's weight. A family module defines, ``hf`` being the
configuration's published keys:

  the mathematics, one sequence at a time, float32, no cache, no kernels
  (callers hold ``jax.default_matmul_precision("highest")``):
    rope_tables(hf, n_tokens) -> (cos, sin) for positions 0 .. n_tokens - 1
    decoder_layer(x, w, cos, sin, hf) -> x     x [T, D]; w is ONE layer's
        weights, float32, a pytree under the names the served ``layers``
        pytree uses (harness/refcheck.py dequantises it leaf by leaf)
    logits(x, final_norm, head, hf) -> [T, V']  for the head columns given
  the arithmetic, of the published keys and of counts the client saw, never
  of the program (the readers under ``layers/`` and the set-up's parameter
  check use it through harness/work.py):
    param_count(hf)            every weight the served model holds
    layer_params(hf)           matmul weights of one layer: what HBM holds
    token_params(hf)           weights ONE token's forward multiplies, all
                               layers, head left out: what prefill flops count
    step_params(hf, tokens)    weights a decode step over ``tokens`` query
                               tokens must READ, all layers and the head; an
                               EXPECTATION where it depends on the tokens,
                               never an upper bound: a need that is overstated
                               reads over 100% of a roofline
    kv_bytes_per_token(hf, element_bytes)     what one token adds to the cache
    q_elements_per_token(hf)   one token's q (and attention output) elements
    attn_flops(hf, pairs)      over (query token, attended token) pairs
  and MAY define (FAMILY_OPTIONAL; a family that defines none is a stack of
  like layers, each row run once, in order, with a cache entry a layer):
    walk(x, layer, rows, leaf, hf) -> x     the order the layers run in.
        x [B, T, D] are the embedded probes; ``layer(x, index)`` applies ONE
        served row of the ``rows`` the stack holds (the harness dequantises
        it and vmaps ``decoder_layer`` over the batch); ``leaf(name)`` is a
        served non-layer leaf in float32 ("final_norm", ...). Which row runs
        when, how often, and what happens to x between is the family's; what
        it returns is what ``logits`` is given
    cache_layers(hf)           layers the K/V cache holds, where that is not
                               ``num_hidden_layers`` (a row that runs twice a
                               token caches twice)
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parents[2]

# a cell file states how hard the cell is driven and what "met" means
CELL_KEYS = {"rate_rps", "clients", "ramp_s", "drain_s", "limits", "knee",
             "knee_rps", "why"}
# a configuration file's own keys; every other top-level key is the
# published config.json, passed verbatim to LlamaConfig.from_hf
CONFIG_KEYS = {"name", "source", "reduced", "assumed", "deployment", "chips",
               "context_size", "engine", "sharding", "reference", "hbm",
               "notes"}


# the family of a configuration file that names none: the dense GQA decoder
DEFAULT_FAMILY = "llama_family"
FAMILY_CONTRACT = ("rope_tables", "decoder_layer", "logits", "param_count",
                   "layer_params", "token_params", "step_params",
                   "kv_bytes_per_token", "q_elements_per_token", "attn_flops")
# not asked for; where a module binds one, it binds a function
FAMILY_OPTIONAL = ("walk", "cache_layers")


class SpecError(ValueError):
    pass


def _read(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{path} does not exist") from None


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with its files loaded."""

    name: str
    chips: int
    config_name: str
    config_file: Path
    config: dict            # benchmark/configs/<config>.json, whole
    family_file: Path       # benchmark/reference/<reference.family>.py
    traffic: dict           # benchmark/traffic/<traffic>.json, whole
    drive: dict             # benchmark/cells/<name>.json, whole
    end_to_end: tuple       # metric entries this cell reports, --trace 0
    per_layer: tuple        # ... and with --trace 1 (alone) or 2 (both)
    run_seconds: int

    @property
    def published(self) -> dict:
        """The configuration's published keys (what from_hf is given)."""
        return {k: v for k, v in self.config.items()
                if k not in CONFIG_KEYS}

    @property
    def max_slots(self) -> int:
        return int(self.config["engine"]["max_slots"])

    @functools.cached_property
    def family(self):
        """The family module (imports JAX: the parent asks after the window,
        when a reader wants its arithmetic, and never in set-up)."""
        return load_family(self.family_file)


def bench_dir(root: Path = ROOT) -> Path:
    """The benchmark's own directory: the first of BENCHMARK.json's paths."""
    return root / _read(root / "BENCHMARK.json")["paths"][0]


def family_file(config: dict, where: str, root: Path = ROOT) -> Path:
    """The module ``reference.family`` names, checked against the contract
    without importing it (it imports JAX)."""
    name = config.get("reference", {}).get("family", DEFAULT_FAMILY)
    path = bench_dir(root) / "reference" / f"{name}.py"
    try:
        body = ast.parse(path.read_text()).body
    except FileNotFoundError:
        raise SpecError(f"{where}: reference.family {name!r} names no file: "
                        f"{path} does not exist") from None
    defined, constants = set(), set()
    for node in body:
        if isinstance(node, ast.FunctionDef):
            defined.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            defined |= {a.asname or a.name for a in node.names}
        elif isinstance(node, ast.Assign):
            names = {t.id for t in node.targets if isinstance(t, ast.Name)}
            defined |= names
            if isinstance(node.value, ast.Constant):
                constants |= names
    missing = [n for n in FAMILY_CONTRACT if n not in defined]
    if missing:
        raise SpecError(f"{path}: a family module defines {FAMILY_CONTRACT} "
                        f"(harness/spec.py); this one lacks {missing}")
    no_function = sorted(constants.intersection(FAMILY_OPTIONAL))
    if no_function:
        raise SpecError(f"{path}: {no_function} must be functions where a "
                        f"family module defines them (harness/spec.py)")
    return path


def _load_module(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(path: Path):
    """Import a family module by path. Its sibling imports
    (``from reference import ...``) resolve through the ``benchmark``
    directory on ``sys.path``, which every entry point puts there."""
    return _load_module("family_" + path.stem, path)


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SpecError(
            f"no workload {workload!r} in BENCHMARK.json; have "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _read(root / cfg_entry["file"])
    reference = config.get("reference", {})
    if not isinstance(reference.get("epsilon"), (int, float)):
        raise SpecError(f"{cfg_entry['file']}: no reference.epsilon (the "
                        f"largest shortfall the reference check allows)")
    if reference.get("statistic", "max") not in ("max", "mean"):
        raise SpecError(f"{cfg_entry['file']}: reference.statistic is 'max' "
                        f"(the default) or 'mean'")
    family = family_file(config, cfg_entry["file"], root)
    base = bench_dir(root)
    drive = _read(base / "cells" / f"{workload}.json")
    unknown = set(drive) - CELL_KEYS
    if unknown:
        raise SpecError(f"cells/{workload}.json: unknown keys "
                        f"{sorted(unknown)}")
    from harness import traffic as trf

    traffic = trf.validate(
        _read(base / "traffic" / f"{entry['traffic']}.json"),
        f"traffic/{entry['traffic']}.json")
    if (traffic["loop"] == "open") == ("clients" in drive):
        raise SpecError(
            f"cells/{workload}.json: an open-loop mix takes rate_rps, a "
            f"closed-loop mix takes clients")
    e2e = tuple(m for m in bench["end_to_end"] if _reported(m, workload))
    names = {m["name"] for m in e2e}
    # a per-layer metric is reported only where the metric it moves is (the
    # contract's rule): ``moves`` says what the layer should move, and a
    # ``workloads`` list narrows the cells further
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reported(m, workload) and m["moves"] in names)
    return Cell(name=workload, chips=int(entry["chips"]),
                config_name=entry["config"],
                config_file=root / cfg_entry["file"], config=config,
                family_file=family,
                traffic=traffic, drive=drive,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))


def load_reader(metric: str, root: Path = ROOT) -> Callable[[Any],
                                                            Optional[float]]:
    """``benchmark/layers/<metric>.py``'s ``read(ctx)``: the metric's value,
    or None when there is nothing to read (the metric is then left out)."""
    path = bench_dir(root) / "layers" / f"{metric}.py"
    if not path.exists():
        raise SpecError(f"per-layer metric {metric!r} has no reader {path}")
    return _load_module(
        "layer_" + metric.replace(".", "_").replace("-", "_"), path).read
