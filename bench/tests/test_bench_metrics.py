"""Every metric's reader on a hand-built run: what it reads, and that a
per-layer reader returns nothing where there is nothing to read."""
import types

import pytest

from bench import spec, traffic

V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
LAUNCH = {"kind": "chain", "in": (8, 8, 128), "sides": [],
          "convs": [(1, 1, 128, 128, 8, 8)], "out": (8, 8, 128)}


def _run(trace=True):
    reqs = [traffic.Request(i, i, due=float(i), sent=i + 0.001 * i,
                            done=i + 0.5) for i in range(100)]
    records = [{"batch_id": i // 4, "batch_size": 4, "status": "ok",
                "queue_wait_s": 0.001 * (i % 10), "execute_s": 0.002}
               for i in range(100)]
    # two program executions of 10 s: launch busy 2 s + 1 s, a pad 1 s
    ops = [("pad.1", 0.0, 1.0), ("_run_chain.1", 1.0, 3.0),
           ("_run_chain.1", 12.0, 13.0)]
    tr = {"ops": ops, "modules": [("jit_fn", 0.0, 10.0),
                                  ("jit_fn", 11.0, 19.0)],
          "t0": 0.0, "t1": 20.0, "host": []} if trace else None
    return types.SimpleNamespace(
        window=traffic.Window(0.0, 100.0, reqs), seconds=100.0,
        setup_s=31.5, records=records, pad_s=[0.002, 0.004] if trace else [],
        trace=tr, launches=[LAUNCH], batch=32, peak=V5E,
        ops_per_image=1_000_000_000)


def test_readers_on_a_hand_built_run():
    run = _run()
    read = lambda name: spec.metric(name).read(run)        # noqa: E731
    assert read("images_per_s") == pytest.approx(1.0)
    assert read("setup_s") == 31.5
    assert read("batch_images_mean") == 4
    assert read("pad_ms_per_batch") == pytest.approx(3.0)
    assert read("device_idle_share") == pytest.approx(100 * (1 - 4 / 20))
    assert read("outside_kernel_share") == pytest.approx(25.0)
    # 32 images x 1e9 ops per program, programs start 11 s apart
    assert read("step_mfu") == pytest.approx(100 * 32e9 / (11 * 393e12))
    # 2 whole executions x the launch's roofline time over its 3 s
    from bench import roofline
    want = 100 * 2 * roofline.roofline_s(LAUNCH, 32, V5E) / 3.0
    assert read("chain_roofline") == pytest.approx(want)
    assert read("horizontal_roofline") is None


@pytest.mark.parametrize("name", ["pad_ms_per_batch", "device_idle_share",
                                  "outside_kernel_share", "step_mfu",
                                  "chain_roofline", "horizontal_roofline"])
def test_readers_return_nothing_without_a_trace(name):
    assert spec.metric(name).read(_run(trace=False)) is None
