"""Every cell of BENCHMARK.json resolves its parts by name, and the file
keeps to the benchmark's own rules."""
import json
import os
import re

import pytest

from bench import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_config_traffic_and_metrics(cell):
    cfg = spec.config(cell["config"])
    assert cfg["name"] == cell["config"]
    specs = spec.reference(cfg["reference"]).layers(cfg)
    assert specs[0]["op"] == "input"
    mix = spec.traffic(cell["traffic"])
    assert callable(spec.loop(mix["loop"]).drive)
    front = spec.front(mix["front"]["kind"])
    assert callable(front.build) and callable(front.batches)
    e2e = spec.metrics_of(BENCH, cell["name"], "end_to_end")
    per = spec.metrics_of(BENCH, cell["name"], "per_layer")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert per
    for m in e2e:
        mod = spec.metric(m["name"])
        assert mod.UNIT == m["unit"] and callable(mod.read)
    reported = {m["name"] for m in e2e}
    for m in per:
        mod = spec.metric(m["name"])
        assert mod.LAYER == m["layer"] and mod.UNIT == m["unit"]
        assert callable(mod.read)
        assert m["moves"] in reported


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["reduced"] == spec.config(c["name"])["reduced"]
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_roofline_shares_are_named_for_kernels():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_every_file_under_the_benchmark_is_named_by_a_name():
    for dirpath, _, files in os.walk(spec.BENCH):
        if os.sep + "out" in dirpath or "__pycache__" in dirpath:
            continue
        for f in files:
            stem = os.path.splitext(f)[0]
            assert re.match(r"^[A-Za-z0-9_.-]+$", stem), f
