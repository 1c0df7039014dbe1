"""Find a cell's files by name: BENCHMARK.json, its configuration, traffic
mix, per-cell parameters and per-layer metric readers.

Everything a cell needs is data found by name, so a later change adds a
cell, configuration, mix or metric by adding files and entries:

  * ``BENCHMARK.json`` (repository root): cells and metrics
  * ``bench/configs/<config>.json``: model sizes as published, the cut,
    and the precision served; its ``"arch"`` names the architecture module
  * ``bench/archs/<arch>.py`` (``"dense"`` when the config names none):
    the configuration's sizes, program config, weights, plain reference
    and work counts
  * ``bench/mixes/<traffic>.json``: traffic generator parameters and the
    engine shape
  * ``bench/cells/<workload>.json`` (optional): parameters of one cell,
    such as the arrival rate and the correctness limit
  * ``bench/metrics/<metric>.py``, or ``<name before the first dot>.py``:
    the reader of a per-layer metric
  * ``bench/peaks.json``: chip peaks keyed by ``device_kind``
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str) -> dict:
    return _json(os.path.join(HERE, "configs", f"{name}.json"))


def load_mix(name: str) -> dict:
    return _json(os.path.join(HERE, "mixes", f"{name}.json"))


def load_cell(workload: str, bench: dict | None = None) -> dict:
    """The cell ``workload`` with its configuration, mix, parameters and the
    metrics it reports, all resolved by name."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[workload]
    cpath = os.path.join(HERE, "cells", f"{workload}.json")
    params = _json(cpath) if os.path.exists(cpath) else {}
    mix = load_mix(w["traffic"])
    reports = lambda m: "workloads" not in m or workload in m["workloads"]
    return {
        "name": workload,
        "chips": w["chips"],
        "config": load_config(w["config"]),
        "mix": mix,
        "engine": dict(mix["engine"], **params.get("engine", {})),
        "params": params,
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


@functools.lru_cache(maxsize=None)
def _module(path: str, name: str):
    """The module at ``path``, executed once per path."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read`` function of a per-layer metric's own file."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return _module(path,
                           f"bench_metric_{stem.replace('.', '_')}").read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                            f"under {os.path.join(HERE, 'metrics')}")


def arch(config: dict):
    """The architecture module a configuration names with ``"arch"``
    (``"dense"`` when it names none): ``bench/archs/<arch>.py``, loaded once
    per file."""
    name = config.get("arch", "dense")
    where = os.path.join(HERE, "archs")
    path = os.path.join(where, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no architecture module {name!r} under "
                                f"{where}")
    return _module(path, f"bench_arch_{name}")


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["chips"]:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json; have {sorted(table['chips'])}")
    return table["chips"][device_kind]


def sizes(config: dict) -> dict:
    """The model sizes the reference and the work counts read."""
    return arch(config).sizes(config)


def program_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    return arch(config).program_config(config)


def policy():
    """The precision every configuration is served in: MXSF weights and
    activations in 1x64 blocks, an MXSF-packed KV cache, bfloat16 compute
    (``compute_dtype`` of the program config)."""
    from repro.core.policy import MXSF_INFER
    return MXSF_INFER.replace(kv_cache_fmt="mxsf")
