"""Content-addressed model zoo: round trips, source-indexed reopen through
``compile_model``, LRU eviction, and sidecar robustness."""
import os

import numpy as np
import pytest

from repro import asm
from repro.core import executor, pathsearch, quantize
from repro.hw import ZU2
from repro.obs.metrics import MetricsRegistry
from repro.runtime import Session
from repro.stages import StageCache, compile_model
from repro.zoo import ModelZoo
from tests.conftest import make_toy_resnet_graph, toy_params


@pytest.fixture(scope="module")
def toy():
    g = make_toy_resnet_graph()
    params = toy_params(g)
    x = np.random.default_rng(0).standard_normal(
        g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm


@pytest.fixture(scope="module")
def toy_artifacts(toy):
    """Three distinct artifacts of the same net (different strategies)."""
    g, qm = toy
    return g, qm, [asm.compile_strategy(g, s, ZU2, qm=qm)
                   for s in (pathsearch.search(g, ZU2),
                             pathsearch.greedy(g, ZU2),
                             pathsearch.naive(g, ZU2))]


def test_put_get_open_round_trip(toy_artifacts, tmp_path):
    g, qm, (art, *_) = toy_artifacts
    zoo = ModelZoo(str(tmp_path / "zoo"))
    key = zoo.put(art, name="toy")
    assert zoo.key_for(art) == key
    art2 = zoo.get(key)
    assert asm.strategy_signature(art2) == asm.strategy_signature(art)
    assert art2.instrs == art.instrs
    co = zoo.open(key)
    assert co.key == key
    [rec] = zoo.list()
    assert rec["name"] == "toy" and rec["key"] == key
    assert rec["size_bytes"] == os.path.getsize(
        os.path.join(zoo.root, key + ".npz"))
    # idempotent re-put: same key, still one entry
    assert zoo.put(art) == key and len(zoo) == 1


def test_compile_model_reopens_from_zoo_without_compiling(toy, tmp_path):
    """Cold call compiles and shelves; a fresh process-equivalent (empty
    stage cache) reopens from the zoo and builds ZERO stages past wrap."""
    g, qm = toy
    zoo = ModelZoo(str(tmp_path / "zoo"))
    co1 = compile_model(g, qm, ZU2, zoo=zoo, name="toy",
                        cache=StageCache(registry=MetricsRegistry()))
    assert len(zoo) == 1
    reg = MetricsRegistry()
    co2 = compile_model(g, qm, ZU2, zoo=zoo,
                        cache=StageCache(registry=reg))
    assert co2.key == co1.key
    assert co2.stage_keys == co1.stage_keys
    for stage in ("lowered", "planned", "compiled"):
        assert reg.get(f"stages.{stage}.misses") is None   # never built
    # bit-exact across the reopen
    x = np.random.default_rng(2).integers(-128, 127,
                                          g.shape("data"), np.int8)
    got = Session.from_artifact(co2.artifact).run(x)
    want = Session.from_artifact(co1.artifact).run(x)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def test_zoo_lru_eviction_and_counters(toy_artifacts, tmp_path):
    g, qm, arts = toy_artifacts
    from repro.obs.metrics import REGISTRY
    zoo = ModelZoo(str(tmp_path / "zoo"), max_entries=2)
    keys = [zoo.put(a) for a in arts[:2]]
    zoo.get(keys[0])                     # refresh: keys[1] becomes LRU
    before = (REGISTRY.get("zoo.evictions").value
              if REGISTRY.get("zoo.evictions") else 0.0)
    k3 = zoo.put(arts[2])                # over capacity: evicts keys[1]
    assert len(zoo) == 2
    assert zoo.get(keys[1]) is None
    assert zoo.get(keys[0]) is not None and zoo.get(k3) is not None
    assert REGISTRY.get("zoo.evictions").value == before + 1


def test_zoo_max_bytes_bound(toy_artifacts, tmp_path):
    g, qm, arts = toy_artifacts
    zoo = ModelZoo(str(tmp_path / "zoo"))
    k1 = zoo.put(arts[0])
    size = zoo.list()[0]["size_bytes"]
    zoo.max_bytes = size + size // 2     # room for one entry only
    zoo.put(arts[1])
    assert len(zoo) == 1 and zoo.get(k1) is None


def test_zoo_tolerates_corrupt_sidecar(toy_artifacts, tmp_path):
    g, qm, (art, *_) = toy_artifacts
    zoo = ModelZoo(str(tmp_path / "zoo"))
    key = zoo.put(art)
    with open(os.path.join(zoo.root, key + ".json"), "w") as f:
        f.write("{not json")
    assert zoo.list() == []              # skipped, not crashed
    assert zoo.get(key) is not None      # the npz itself is still readable


# ------------------------------------------------- corruption (ISSUE 10)
def test_load_artifact_truncated_npz_raises_artifact_error(toy_artifacts,
                                                           tmp_path):
    g, qm, (art, *_) = toy_artifacts
    path = str(tmp_path / "art.npz")
    asm.save_artifact(art, path)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])   # truncated mid-archive
    with pytest.raises(asm.ArtifactError, match="corrupt artifact"):
        asm.load_artifact(path)
    # still a ValueError subclass: pre-existing guards keep working
    with pytest.raises(ValueError):
        asm.CompiledArtifact.load(path)


def test_load_artifact_garbage_bytes_raise_artifact_error(tmp_path):
    path = str(tmp_path / "garbage.npz")
    with open(path, "wb") as f:
        f.write(b"this is not an npz archive at all")
    with pytest.raises(asm.ArtifactError, match="corrupt artifact"):
        asm.load_artifact(path)


def test_load_artifact_missing_file_stays_file_not_found(tmp_path):
    with pytest.raises(FileNotFoundError):
        asm.load_artifact(str(tmp_path / "never-saved.npz"))


def test_load_artifact_tampered_metadata_raises_artifact_error(toy_artifacts,
                                                               tmp_path):
    import zipfile as zf
    g, qm, (art, *_) = toy_artifacts
    path = str(tmp_path / "art.npz")
    asm.save_artifact(art, path)
    # npz archives are zips: rewrite the metadata member with non-JSON bytes
    tampered = str(tmp_path / "tampered.npz")
    with zf.ZipFile(path) as zin, zf.ZipFile(tampered, "w") as zout:
        for item in zin.infolist():
            data = zin.read(item.filename)
            if item.filename == "meta_json.npy":
                data = data[:len(data) // 2]
            zout.writestr(item, data)
    with pytest.raises(asm.ArtifactError, match="corrupt artifact"):
        asm.load_artifact(tampered)


def test_zoo_get_corrupt_npz_raises_artifact_error_with_key(toy_artifacts,
                                                            tmp_path):
    g, qm, (art, *_) = toy_artifacts
    zoo = ModelZoo(str(tmp_path / "zoo"))
    key = zoo.put(art)
    with open(os.path.join(zoo.root, key + ".npz"), "wb") as f:
        f.write(b"\x00" * 64)
    with pytest.raises(asm.ArtifactError, match=key[:16]):
        zoo.get(key)
    assert zoo.remove(key)              # the advertised cleanup works
    assert zoo.get(key) is None


def test_zoo_get_tampered_sidecar_key_raises_artifact_error(toy_artifacts,
                                                            tmp_path):
    import json as jsonlib
    g, qm, (art, *_) = toy_artifacts
    zoo = ModelZoo(str(tmp_path / "zoo"))
    key = zoo.put(art)
    side = os.path.join(zoo.root, key + ".json")
    rec = jsonlib.load(open(side))
    rec["key"] = "someone-elses-key"
    with open(side, "w") as f:
        jsonlib.dump(rec, f)
    with pytest.raises(asm.ArtifactError, match="tampered"):
        zoo.get(key)


# ---------------------------------------------- concurrent writers (lock)
def test_zoo_concurrent_writers_keep_index_consistent(toy_artifacts,
                                                      tmp_path):
    """Hammer one store from many threads (flock serializes per open fd, so
    in-process threads exercise the same lock path as processes): every
    put/evict interleaving must leave readable sidecars and npz/json pairs."""
    import threading

    g, qm, arts = toy_artifacts
    zoo = ModelZoo(str(tmp_path / "zoo"), max_entries=2)
    errs = []

    def writer(art, n=6):
        try:
            for _ in range(n):
                key = zoo.put(art, name="hammer")
                zoo.get(key)             # may be None if another evicted it
                zoo.evict()
        except Exception as e:           # pragma: no cover - the failure path
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(a,)) for a in arts
               for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    # the index is consistent: every listed record reloads bit-true (the
    # sidecar's recorded key is validated against the filename by get)
    recs = zoo.list()
    assert len(recs) <= 2                # the bound held under concurrency
    for rec in recs:
        art = zoo.get(rec["key"])
        assert art is None or art.graph_sig == arts[0].graph_sig
