"""Finds every part of a cell by name: BENCHMARK.json at the checkout's root,
``configs/<config>.json`` with its reference ``configs/<config>.py``,
``traffic/<mix>.json`` with the loop (``loops/<loop>.py``) and front end
(``fronts/<kind>.py``) it names, and ``metrics/<metric>.py``."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(os.path.join(BENCH, "configs", f"{name}.json"))


def reference(name: str):
    """The configuration's plain reference module (its ``layers(cfg)``)."""
    return _module(os.path.join(BENCH, "configs", f"{name}.py"),
                   f"bench_ref_{name}")


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH, "traffic", f"{name}.json"))


def loop(name: str):
    """A traffic loop: ``drive(mix, seed, clock, send)``."""
    return _module(os.path.join(BENCH, "loops", f"{name}.py"),
                   f"bench_loop_{name}")


def front(name: str):
    """A front end of the program: ``build(session, params, observers)``,
    ``padded_batch(params)`` and ``batches(front)``."""
    return _module(os.path.join(BENCH, "fronts", f"{name}.py"),
                   f"bench_front_{name}")


def metric(name: str):
    """A metric's reader: ``UNIT`` and ``read(run)``, and for a per-layer
    metric its ``LAYER``."""
    return _module(os.path.join(BENCH, "metrics", f"{name}.py"),
                   f"bench_metric_{name}")


def metrics_of(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``workload`` reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def _module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(
        modname.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
