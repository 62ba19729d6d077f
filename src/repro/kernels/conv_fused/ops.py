"""Launcher + executor bridge for the fused chain kernel.

``run_launch``      — execute one ``lower.FusedLaunch`` against an activation
                      env.  This is the ``Int8Executor`` dispatch hook: the
                      launch already carries every resolved parameter, so NO
                      graph inspection or pattern matching happens at run
                      time — lowering decided everything at compile time.
``fused_conv_block``— legacy single-conv(+tail) wrapper (kernel tests,
                      micro-benchmarks).
``supports``        — static support predicate of the chain kernel.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.conv_fused.conv_fused import (
    I8_MIN, chain_geometry, fused_chain_pallas, fused_horizontal_pallas)


def _tile_rows(oh: int, pref=(8, 4, 2, 1)) -> int:
    for t in pref:
        if oh % t == 0:
            return t
    return 1


def _tile_oc(oc: int, n_conv: int = 1) -> int:
    """T_oc heuristic: 128 (one MXU panel) where it divides OC, else the full
    OC — the only extents the (8, 128) block rule admits on the lane axis.
    A chain with convs upstream of its final conv runs the full OC: those
    stages are recomputed for every OC tile."""
    return 128 if (n_conv == 1 and oc % 128 == 0) else oc


def _legal_tw(tw: int, ow: int) -> int:
    """A width tile is the full width or a multiple of 8 (sublane block rule;
    it also keeps the kernel's dynamic width offsets 8-aligned)."""
    return ow if tw >= ow else min(ow, -(-tw // 8) * 8)


def _legal_toc(toc: int, oc: int) -> bool:
    """An OC tile the chip admits: a 128-multiple divisor of OC, or OC."""
    return toc == oc or (oc % toc == 0 and toc % 128 == 0)


def _resolve_tile(tile, oh: int, ow: int, oc: int, n_conv: int) -> tuple:
    """(th, tw, toc) the launch executes.

    A serialized tile shape (``FusedLaunch.tile``, chosen by the tile-shape
    search) wins, clamped to the output extents and legalized for the chip's
    block rule: T_w rounds up to a multiple of 8 (or the full width) and a
    T_oc that is neither a 128-multiple divisor of OC nor OC itself is
    replaced by the heuristic, with a warning — the OC grid axis cannot run
    ragged and Mosaic refuses misaligned lane blocks.  Without a shape: row
    tiles from the largest divisor, full width, heuristic T_oc.
    """
    if not tile:
        return (_tile_rows(oh), ow,
                _tile_oc(oc, n_conv) if n_conv else oc)
    th = max(1, min(int(tile[0]), oh))
    tw = _legal_tw(max(1, int(tile[1])), ow)
    toc = max(1, min(int(tile[2]), oc))
    if not n_conv:
        toc = oc
    elif not _legal_toc(toc, oc):
        legal = _tile_oc(oc, n_conv)
        warnings.warn(f"tile T_oc={toc} is not a legal lane block for "
                      f"OC={oc}; running T_oc={legal}", stacklevel=2)
        toc = legal
    return th, tw, toc


def interpret_mode() -> bool:
    """Kernels run under the Pallas interpreter exactly when the default
    backend is the CPU; on an accelerator every launch is compiled."""
    return jax.default_backend() == "cpu"


def supports(*, depthwise=False, **_ignored) -> bool:
    """What the chain kernel accepts.  Depthwise convolution is the only
    structural exclusion; dilation, anisotropic strides/kernels and
    ceil/padded pool tails are all handled by the staged kernel's
    padded-coordinate masking (extra keyword capabilities are accepted for
    historical call sites and ignored)."""
    return not depthwise


def _pad_to(x, top: int, left: int, h_req: int, w_req: int, fill: int):
    n, h, w, c = x.shape
    bottom = max(0, h_req - top - h)
    right = max(0, w_req - left - w)
    return jnp.pad(x, ((0, 0), (top, bottom), (left, right), (0, 0)),
                   constant_values=np.int8(fill))


@partial(jax.jit, static_argnames=("chain", "oh", "ow", "oc", "interpret",
                                   "tile"))
def _run_chain(x, weights, biases, sides, *, chain, oh, ow, oc, interpret,
               tile=()):
    n_conv = sum(1 for st in chain if st[0] == "conv")
    th, tw, toc = _resolve_tile(tile, oh, ow, oc, n_conv)
    geom = chain_geometry(chain, th, oh, ow, tw)
    xp = _pad_to(x, geom["q_in"][0], geom["q_in"][1],
                 geom["h_req"], geom["w_req"], geom["fill0"])
    sp = tuple(_pad_to(s, sg["q"][0], sg["q"][1], sg["h_req"], sg["w_req"], 0)
               for s, sg in zip(sides, geom["sides"]))
    biases = tuple(b.reshape(1, -1) for b in biases)
    return fused_chain_pallas(xp, weights, biases, sp, chain=chain, th=th,
                              tw=tw, toc=toc, oh=oh, ow=ow, oc=oc,
                              interpret=interpret)


@partial(jax.jit, static_argnames=("stride", "pad", "oh", "ow", "interpret",
                                   "tile"))
def _run_horizontal(x, w, b, shift_vec, relu_vec, *, stride, pad, oh, ow,
                    interpret, tile=()):
    kh, kw = w.shape[:2]
    sh, sw = stride
    th, tw, toc = _resolve_tile(tile, oh, ow, int(w.shape[-1]), 1)
    n_h = -(-oh // th)
    n_w = -(-ow // tw)
    xp = _pad_to(x, pad[0], pad[1], (n_h * th - 1) * sh + kh,
                 (n_w * tw - 1) * sw + kw, 0)
    b, shift_vec, relu_vec = (v.reshape(1, -1)
                              for v in (b, shift_vec, relu_vec))
    return fused_horizontal_pallas(xp, w, b, shift_vec, relu_vec,
                                   stride=stride, th=th, tw=tw, toc=toc,
                                   oh=oh, ow=ow, interpret=interpret)


# ------------------------------------------------------------ executor hook
def run_launch(launch, env: dict, qm) -> dict:
    """Execute one FusedLaunch; returns {tensor name: int8 array}."""
    interpret = interpret_mode()
    if launch.kind == "horizontal":
        x = env[launch.in_name]
        w = jnp.concatenate(
            [jnp.asarray(qm.weights[m]) for m, *_ in launch.members], axis=-1)
        b = jnp.concatenate(
            [jnp.asarray(qm.biases[m]) for m, *_ in launch.members])
        shift_vec = jnp.asarray(np.concatenate(
            [np.full(oc, s, np.int32) for _, oc, s, _ in launch.members]))
        relu_vec = jnp.asarray(np.concatenate(
            [np.full(oc, int(r), np.int32) for _, oc, _, r in launch.members]))
        oh, ow = launch.out_hw
        y = _run_horizontal(x, w, b, shift_vec, relu_vec,
                            stride=tuple(launch.stride),
                            pad=tuple(launch.pad), oh=oh, ow=ow,
                            interpret=interpret,
                            tile=tuple(launch.tile))
        outs, off = {}, 0
        for m, oc_m, _, _ in launch.members:
            outs[m] = y[..., off:off + oc_m]
            off += oc_m
        return outs

    x = env[launch.in_name]
    if launch.fc_reshape:
        x = x.reshape(x.shape[0], 1, 1, -1)
    weights, biases = [], []
    for st in launch.stages:
        if st[0] == "conv":
            w = jnp.asarray(qm.weights[st[1]])
            if launch.fc_reshape:
                w = w.reshape(1, 1, *w.shape)
            weights.append(w)
            biases.append(jnp.asarray(qm.biases[st[1]]))
    sides = tuple(env[s] for s in launch.sides)
    oh, ow = launch.out_hw
    oc = int(weights[-1].shape[-1]) if weights else int(x.shape[-1])
    y = _run_chain(x, tuple(weights), tuple(biases), sides,
                   chain=launch.stages, oh=oh, ow=ow, oc=oc,
                   interpret=interpret, tile=tuple(launch.tile))
    return {launch.out_name: y}


# ------------------------------------------------------------ legacy wrapper
def fused_conv_block(x, w, b, *, stride=(1, 1), pad=(0, 0), shift=0,
                     relu=False, pool=None, eltwise=None):
    """Single conv (+maxpool | +eltwise) as a 1-2 stage chain.

    eltwise = (side, s_conv, s_side, relu_out) or None; pool = (kp, sp) with
    VALID floor semantics (the historical test contract)."""
    n, h, w_, ic = x.shape
    kh, kw, _, oc = w.shape
    sh, sw = stride
    ph, pw = pad
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w_ + 2 * pw - kw) // sw + 1
    stages = [("conv", "w0", kh, kw, sh, sw, ph, pw, 1, 1,
               int(shift), bool(relu), oh, ow)]
    sides = ()
    if pool is not None:
        kp, sp = pool
        oh = (oh - kp) // sp + 1
        ow = (ow - kp) // sp + 1
        stages.append(("pool", "p0", "max", kp, kp, sp, sp, 0, 0, oh, ow,
                       kp * kp))
    if eltwise is not None:
        side, s_conv, s_side, relu_out = eltwise
        stages.append(("elt", "e0", int(s_conv), int(s_side),
                       bool(relu_out), oh, ow))
        sides = (side,)
    return _run_chain(x, (w,), (b,), sides, chain=tuple(stages), oh=oh,
                      ow=ow, oc=oc, interpret=interpret_mode())
