"""conv_fused Pallas kernel: bit-exact vs the int8 oracle across a
shape/stride/pool/eltwise sweep (interpret mode on the CPU, compiled on
a chip)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # dev-only dep (requirements-dev.txt); only the property sweep needs it
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels.conv_fused.ops import (fused_conv_block, interpret_mode,
                                          supports)
from repro.kernels.conv_fused.ref import fused_conv_ref


def _data(rng, h, w, ic, oc, k):
    x = rng.integers(-128, 128, (1, h, w, ic)).astype(np.int8)
    wt = rng.integers(-128, 128, (k, k, ic, oc)).astype(np.int8)
    b = rng.integers(-2000, 2000, oc).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(wt), jnp.asarray(b)


CASES = [
    # (h, w, ic, oc, k, stride, pad, relu, shift)
    (8, 8, 4, 8, 3, 1, 1, True, 6),
    (8, 8, 4, 8, 3, 1, 1, False, 6),
    (9, 9, 3, 5, 3, 1, 0, True, 7),       # ragged dims
    (12, 12, 8, 16, 5, 1, 2, True, 8),
    (12, 12, 8, 16, 3, 2, 1, True, 7),    # stride 2
    (16, 16, 16, 4, 1, 1, 0, True, 5),    # 1x1
    (7, 7, 2, 3, 3, 2, 1, False, 4),      # everything ragged
]


@pytest.mark.parametrize("h,w,ic,oc,k,s,p,relu,shift", CASES)
def test_plain_conv_bit_exact(h, w, ic, oc, k, s, p, relu, shift):
    rng = np.random.default_rng(h * w + oc)
    x, wt, b = _data(rng, h, w, ic, oc, k)
    got = fused_conv_block(x, wt, b, stride=(s, s), pad=(p, p), shift=shift,
                           relu=relu)
    want = fused_conv_ref(x, wt, b, stride=(s, s), pad=(p, p), shift=shift,
                          relu=relu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


POOL_CASES = [
    # (h, w, ic, oc, k, pad, kp, sp)
    (8, 8, 4, 8, 3, 1, 2, 2),
    (10, 10, 4, 8, 3, 1, 2, 2),
    (8, 8, 4, 8, 3, 1, 3, 1),
    (12, 12, 3, 6, 5, 2, 2, 2),
    (14, 14, 8, 16, 3, 1, 3, 1),
]


@pytest.mark.parametrize("h,w,ic,oc,k,p,kp,sp", POOL_CASES)
def test_conv_pool_bit_exact(h, w, ic, oc, k, p, kp, sp):
    rng = np.random.default_rng(h + kp * 10)
    x, wt, b = _data(rng, h, w, ic, oc, k)
    oh = h + 2 * p - k + 1
    assert supports(kernel=(k, k), stride=(1, 1), pool=(kp, sp),
                    conv_oh=oh, conv_ow=oh)
    got = fused_conv_block(x, wt, b, stride=(1, 1), pad=(p, p), shift=7,
                           relu=True, pool=(kp, sp))
    want = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(p, p), shift=7,
                          relu=True, pool=(kp, sp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("relu_out", [False, True])
def test_conv_eltwise_bit_exact(relu_out):
    rng = np.random.default_rng(3)
    x, wt, b = _data(rng, 8, 8, 4, 8, 3)
    side = jnp.asarray(rng.integers(-128, 128, (1, 8, 8, 8)).astype(np.int8))
    elt = (side, 1, 2, relu_out)
    got = fused_conv_block(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                           relu=False, eltwise=elt)
    want = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                          relu=False, eltwise=elt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(4, 12), st.integers(4, 12),
           st.sampled_from([1, 2, 3, 4]), st.sampled_from([1, 2, 4, 8]),
           st.sampled_from([1, 3]), st.integers(0, 10), st.booleans())
    def test_property_sweep(h, w, ic, oc, k, shift, relu):
        rng = np.random.default_rng(h * 31 + w)
        x, wt, b = _data(rng, h, w, ic, oc, k)
        p = (k - 1) // 2
        got = fused_conv_block(x, wt, b, stride=(1, 1), pad=(p, p),
                               shift=shift, relu=relu)
        want = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(p, p),
                              shift=shift, relu=relu)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_support_predicate():
    # depthwise is the chain kernel's only structural exclusion
    assert not supports(kernel=(3, 3), stride=(1, 1), depthwise=True)
    # the staged kernel's padded-coordinate masking handles all of these
    assert supports(kernel=(3, 3), stride=(1, 1), dilation=(2, 2))
    assert supports(kernel=(3, 3), stride=(1, 2))
    assert supports(kernel=(3, 3), stride=(1, 1), pool=(3, 2),
                    conv_oh=8, conv_ow=8)   # ceil-extended pool windows


def test_dilated_conv_bit_exact():
    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(11)
    x, wt, b = _data(rng, 12, 12, 4, 8, 3)
    want = int8_ops.conv2d(x, wt, b, stride=(1, 1), pad=(2, 2),
                           dilation=(2, 2), shift=6, relu=True)
    chain = (("conv", "c", 3, 3, 1, 1, 2, 2, 2, 2, 6, True, 12, 12),)
    got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=12, ow=12, oc=8,
                     interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ceil_pool_chain_bit_exact():
    """conv -> maxpool with pool padding AND a ceil-extended last window —
    the pre-padded-slack path the lowering pass emits for ResNet's pool1."""
    import math

    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(12)
    x, wt, b = _data(rng, 13, 13, 4, 8, 3)
    y_c = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                         relu=True)
    for kp, sp, pp in [(3, 2, 0), (3, 2, 1), (2, 2, 1)]:
        want = int8_ops.maxpool(y_c, kernel=(kp, kp), stride=(sp, sp),
                                pad=(pp, pp), ceil_mode=True)
        oh = math.ceil((13 + 2 * pp - kp) / sp) + 1
        chain = (("conv", "c", 3, 3, 1, 1, 1, 1, 1, 1, 6, True, 13, 13),
                 ("pool", "p", "max", kp, kp, sp, sp, pp, pp, oh, oh, kp * kp))
        got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=oh, ow=oh,
                         oc=8, interpret=interpret_mode())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_avgpool_chain_bit_exact():
    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(13)
    x, wt, b = _data(rng, 12, 12, 4, 8, 3)
    y_c = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                         relu=True)
    want = int8_ops.avgpool(y_c, kernel=(2, 2), stride=(2, 2))
    chain = (("conv", "c", 3, 3, 1, 1, 1, 1, 1, 1, 6, True, 12, 12),
             ("pool", "p", "avg", 2, 2, 2, 2, 0, 0, 6, 6, 4))
    got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=6, ow=6, oc=8,
                     interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_horizontal_stacked_bit_exact():
    """Two siblings with different shifts/ReLU in one stacked launch must
    match each sibling computed alone (per-channel requantization)."""
    import jax.numpy as jnp

    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_horizontal

    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.integers(-128, 128, (1, 10, 10, 4)).astype(np.int8))
    wa = jnp.asarray(rng.integers(-128, 128, (3, 3, 4, 8)).astype(np.int8))
    wb = jnp.asarray(rng.integers(-128, 128, (3, 3, 4, 12)).astype(np.int8))
    ba = jnp.asarray(rng.integers(-2000, 2000, 8).astype(np.int32))
    bb = jnp.asarray(rng.integers(-2000, 2000, 12).astype(np.int32))
    ya = int8_ops.conv2d(x, wa, ba, stride=(1, 1), pad=(1, 1), shift=5,
                         relu=True)
    yb = int8_ops.conv2d(x, wb, bb, stride=(1, 1), pad=(1, 1), shift=7)
    y = _run_horizontal(
        x, jnp.concatenate([wa, wb], axis=-1), jnp.concatenate([ba, bb]),
        jnp.asarray(np.repeat([5, 7], [8, 12]).astype(np.int32)),
        jnp.asarray(np.repeat([1, 0], [8, 12]).astype(np.int32)),
        stride=(1, 1), pad=(1, 1), oh=10, ow=10, interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(y[..., :8]), np.asarray(ya))
    np.testing.assert_array_equal(np.asarray(y[..., 8:]), np.asarray(yb))
