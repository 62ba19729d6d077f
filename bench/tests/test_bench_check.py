"""The comparison that decides ``correct``, and its control: the reference
computed in int4, the precision below the configuration's int8, has to fail
the limit.  Run at 32x32 input so a CPU test can hold it; the chip readings
at the cells' own size are in PERF.md."""
import numpy as np
import pytest

from bench import check, control, spec


def test_centred_gaps_ignore_what_softmax_ignores():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((3, 10))
    assert np.allclose(check.rel_gaps(ref + 5.0, ref, False), 0.0)
    probs = np.exp(ref) / np.exp(ref).sum(1, keepdims=True)
    assert np.allclose(check.rel_gaps(probs, probs, True), 0.0)
    g = check.rel_gaps(ref[::-1], ref, False)
    assert g[1] == 0.0 and g[0] > 0.5


def test_departures_see_answers_served_to_the_wrong_request():
    # answers share a large common part and differ by a small one per
    # image; a common error moves only the first number, answers moved
    # one row over move only the second
    rng = np.random.default_rng(1)
    ref = 10.0 * rng.standard_normal(100) + rng.standard_normal((16, 100))
    common = check.numbers(ref + 2.0 * rng.standard_normal(100), ref, False)
    assert common["departure_err_max"] < 1e-9
    assert common["logit_rel_err_max"] > 0.1
    moved = check.numbers(np.roll(ref, 1, axis=0), ref, False)
    assert moved["logit_rel_err_max"] < 0.25
    assert moved["departure_err_max"] > 1.0
    # a departure is measured against its own size, or the median's where
    # that is larger: doubled departures read 1 at or above the median
    doubled = check.departure_gaps(2 * ref - ref.mean(0), ref, False)
    assert doubled.max() == pytest.approx(1.0)
    assert (doubled <= 1.0 + 1e-9).all() and np.median(doubled) < 1.0
    with pytest.raises(ValueError):
        check.departure_gaps(ref[:1], ref[:1], False)


def test_verdict_needs_every_number_read_and_within_its_limit():
    ok = {"a": {"value": 0.1, "limit": 0.2}, "n": {"value": 0, "limit": 0}}
    assert check.verdict(ok)
    assert not check.verdict(dict(ok, n={"value": 1, "limit": 0}))
    assert not check.verdict(dict(ok, a={"value": None, "limit": 0.2}))


def test_sample_is_drawn_from_the_seed():
    a = check.sample(1000, 2**31 + 3)
    assert len(a) == check.SAMPLE and len(set(a)) == check.SAMPLE
    assert np.array_equal(a, check.sample(1000, 2**31 + 3))
    assert not np.array_equal(a, check.sample(1000, 2**31 + 4))
    assert list(check.sample(5, 1)) == [0, 1, 2, 3, 4]


SEEDS = [2**31 + 11, 2**31 + 12, 2**31 + 13]


@pytest.fixture(scope="module")
def control_readings():
    cfg = dict(spec.config("resnet50-224"), image_size=32)
    return cfg, dict(zip(SEEDS, control.readings(cfg, SEEDS)))


@pytest.mark.parametrize("seed", SEEDS)
def test_int4_control_fails_the_limit(control_readings, seed):
    cfg, readings = control_readings
    limits = cfg["check"]
    assert set(readings[seed][4]) == set(limits) - {"unfinished"}
    assert any(v > limits[k] for k, v in readings[seed][4].items())
    assert all(v < limits[k] for k, v in readings[seed][8].items())
