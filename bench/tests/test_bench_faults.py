"""A whole run of the harness on the CPU, past its look for a chip: a sound
run comes out correct, and each fault planted in the timed path under it
makes ``correct`` come out false."""
import pytest

from bench import faults, run, spec


@pytest.fixture
def cpu_run(monkeypatch):
    """Runs one cell at 32x32 input and batches of 2, with the persistent
    compile cache left off and JAX's config restored afterwards."""
    import jax
    import repro.jax_cache

    before = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setattr(repro.jax_cache, "enable_compile_cache",
                        lambda: "off")
    cfg = dict(spec.config("resnet50-224"), image_size=32)
    mix = dict(spec.traffic("offline"), outstanding=4, warmup_s=0.2,
               front=dict(kind="server", max_batch=2, allowed_sizes=[2],
                          max_latency_s=0.002))
    bench = spec.benchmark()
    e2e = spec.metrics_of(bench, "resnet50-224.offline", "end_to_end")

    def go():
        return run.run_cell("resnet50-cpu.offline", cfg, mix, e2e,
                            seed=2**31 + 99, seconds=2.0, trace=False,
                            devices=jax.devices())
    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def test_sound_run_is_correct(cpu_run):
    res = cpu_run()
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["images_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert len(res["rows"]["served"]) == len(res["rows"]["ref"]) > 2


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_makes_the_run_incorrect(cpu_run, fault):
    undo = faults.plant(faults.FAULTS[fault])
    try:
        res = cpu_run()
    finally:
        undo()
    assert not res["correct"]
    assert any(v["value"] > v["limit"] for v in res["check"].values())
