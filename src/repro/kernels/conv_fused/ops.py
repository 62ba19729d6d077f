"""Launcher + executor bridge for the fused chain kernel.

``run_launch``      — execute one ``lower.FusedLaunch`` against an activation
                      env.  This is the ``Int8Executor`` dispatch hook: the
                      launch already carries every resolved parameter, so NO
                      graph inspection or pattern matching happens at run
                      time — lowering decided everything at compile time.
``fused_conv_block``— legacy single-conv(+tail) wrapper (kernel tests,
                      micro-benchmarks).
``supports``        — static support predicate of the chain kernel.
``launch_blocks``   — the grid and blocks one launch runs with: its VMEM
                      footprint and the weight bytes it pulls from HBM.
``launch_macs``     — the conv MACs one launch executes and the model's.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.conv_fused.conv_fused import (
    I8_MIN, LaunchBlocks, _true_hw, chain_blocks, chain_geometry,
    fused_chain_pallas, fused_horizontal_pallas, horizontal_blocks)


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


def _padded_hw(h: int, w: int, top: int, left: int, h_req: int,
               w_req: int) -> tuple:
    """Extents of an (h, w) map padded by ``top``/``left`` and out to at
    least (h_req, w_req)."""
    return max(h_req, top + h), max(w_req, left + w)


def _pad_to(x, top: int, left: int, h_req: int, w_req: int, fill: int):
    n, h, w, c = x.shape
    hp, wp = _padded_hw(h, w, top, left, h_req, w_req)
    return jnp.pad(x, ((0, 0), (top, hp - top - h), (left, wp - left - w),
                       (0, 0)), constant_values=np.int8(fill))


def _horizontal_pad(kernel, stride, pad, oh: int, ow: int, th: int,
                    tw: int) -> tuple:
    """(top, left, h_req, w_req) of a horizontal launch's padded input."""
    (kh, kw), (sh, sw) = kernel, stride
    return (pad[0], pad[1], (-(-oh // th) * th - 1) * sh + kh,
            (-(-ow // tw) * tw - 1) * sw + kw)


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
    th, tw, toc = _resolve_tile(tile, oh, ow, int(w.shape[-1]), 1)
    xp = _pad_to(x, *_horizontal_pad(w.shape[:2], stride, pad, oh, ow, th,
                                     tw), 0)
    b, shift_vec, relu_vec = (v.reshape(1, -1)
                              for v in (b, shift_vec, relu_vec))
    return fused_horizontal_pallas(xp, w, b, shift_vec, relu_vec,
                                   stride=stride, th=th, tw=tw, toc=toc,
                                   oh=oh, ow=ow, interpret=interpret)


# ------------------------------------------------------- static launch costs
def _chain_tile(chain, weight_shapes, in_c: int, tile) -> tuple:
    """(th, tw, toc, oc, geometry) a chain launch runs with."""
    oh, ow = _true_hw(chain[-1])
    oc = int(weight_shapes[-1][-1]) if weight_shapes else in_c
    th, tw, toc = _resolve_tile(tuple(tile), oh, ow, oc, len(weight_shapes))
    return th, tw, toc, oc, chain_geometry(chain, th, oh, ow, tw)


def launch_blocks(launch, in_hwc, weight_shapes, side_hwcs=(),
                  batch: int = 1) -> LaunchBlocks:
    """The grid and blocks ``launch`` runs with on a batch of ``batch``:
    the same ``chain_blocks`` / ``horizontal_blocks`` the kernel launches
    with, over the input padded as ``_run_chain`` / ``_run_horizontal`` pad
    it.  ``in_hwc`` is the launch's input per image (an fc's flattened to
    (1, 1, K)), ``weight_shapes`` one (KH, KW, IC, OC) panel per conv stage
    (a horizontal launch: the one OC-stacked panel), ``side_hwcs`` one per
    elt stage."""
    h, w, c = in_hwc
    if launch.kind == "horizontal":
        (ws,) = weight_shapes
        oh, ow = launch.out_hw
        th, tw, toc = _resolve_tile(tuple(launch.tile), oh, ow, ws[-1], 1)
        hp, wp = _padded_hw(h, w, *_horizontal_pad(
            launch.kernel, launch.stride, launch.pad, oh, ow, th, tw))
        return horizontal_blocks((batch, hp, wp, c), ws,
                                 stride=tuple(launch.stride), th=th, tw=tw,
                                 toc=toc, oh=oh, ow=ow)
    chain = launch.stages
    _, _, toc, oc, geom = _chain_tile(chain, weight_shapes, c, launch.tile)
    hp, wp = _padded_hw(h, w, *geom["q_in"], geom["h_req"], geom["w_req"])
    sides = [(batch,) + _padded_hw(sh, sw, *sg["q"], sg["h_req"],
                                   sg["w_req"]) + (sc,)
             for (sh, sw, sc), sg in zip(side_hwcs, geom["sides"])]
    return chain_blocks(chain, geom, (batch, hp, wp, c), weight_shapes,
                        sides, toc=toc, oc=oc)


def launch_macs(launch, in_hwc, weight_shapes) -> tuple:
    """(executed, model) conv multiply-accumulates of ``launch`` per image.
    Executed counts every conv stage at the rows and columns the kernel
    computes: halo rows and columns staged again for each row and width
    tile, ragged tiles' slack, and the stages upstream of the final conv
    once per OC tile.  Model counts the true output extents once."""
    if launch.kind == "horizontal":
        (ws,) = weight_shapes
        kh, kw, ic, oc = ws
        oh, ow = launch.out_hw
        th, tw, _ = _resolve_tile(tuple(launch.tile), oh, ow, oc, 1)
        per_pos = kh * kw * ic * oc
        return (-(-oh // th) * th * -(-ow // tw) * tw * per_pos,
                oh * ow * per_pos)
    chain = launch.stages
    _, _, toc, oc, geom = _chain_tile(chain, weight_shapes, in_hwc[-1],
                                      launch.tile)
    conv_idx = [i for i, st in enumerate(chain) if st[0] == "conv"]
    steps = geom["n_h"] * geom["n_w"] * (oc // toc)
    executed = model = 0
    for i, (kh, kw, ic, oc_i) in zip(conv_idx, weight_shapes):
        per_pos = kh * kw * ic
        depth = toc if i == conv_idx[-1] else oc_i
        executed += steps * geom["rows"][i] * geom["cols"][i] * per_pos * depth
        model += chain[i][12] * chain[i][13] * per_pos * oc_i
    return executed, model


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
