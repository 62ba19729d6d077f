"""CPU rehearsal of ``chip_smoke.py``: its served path on a small graph, with
the kernels in interpret mode (derived from the CPU backend), and its entry
point's refusal to report success without a TPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from tests.conftest import make_toy_resnet_graph, toy_params  # noqa: E402


@pytest.fixture(scope="module")
def toy():
    import numpy as np

    from repro.core import executor, quantize

    g = make_toy_resnet_graph()
    calib = np.random.default_rng(0).standard_normal(
        g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, toy_params(g), calib, executor.run_float)
    return g, qm, chip_smoke.make_requests(g, qm, 8, seed=0)


def test_served_path_bit_exact_without_fallbacks(toy):
    from repro.kernels.conv_fused.ops import interpret_mode

    g, qm, xs = toy
    lines = []
    r = chip_smoke.serve_one_chip(g, qm, xs, log=lines.append)
    assert interpret_mode() and r["interpret"]
    assert r["n_fallbacks"] == 0 and r["n_launches"] > 0
    assert r["n_served"] == len(xs) == 8
    assert any("bit-exact with the int8 reference: 8/8" in s for s in lines)
    assert any(s.startswith("set-up: cold compile") for s in lines)


def test_served_path_flags_a_wrong_output(toy, monkeypatch):
    """The bit-exactness check is live: a served output one bit off fails."""
    from repro.runtime.session import PendingBatch

    g, qm, xs = toy
    real = PendingBatch.results

    def off_by_one(self):
        outs = real(self)
        k = max(outs[0])
        outs[0] = dict(outs[0], **{k: outs[0][k] ^ 1})
        return outs

    monkeypatch.setattr(PendingBatch, "results", off_by_one)
    with pytest.raises(chip_smoke.SmokeError, match="differ"):
        chip_smoke.serve_one_chip(g, qm, xs, log=lambda s: None)


def test_entry_point_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform cpu" in out


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; without
    it the cache goes to the fixed directory of the checkout."""
    import jax

    from repro import jax_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = jax_cache.enable_compile_cache()
        if env_dir is None:
            assert got == jax_cache.DEFAULT_DIR
            assert got == os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
