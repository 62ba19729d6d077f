"""Plain reference forward pass of a CNN given as a list of layer specs.

A configuration's reference module (``configs/<config>.py``) writes its
architecture down as specs, one dict per layer in topological order:

    {"op": "conv", "name", "in", "k", "s", "oc", "bn", "relu"}   'same' pad
    {"op": "maxpool", "name", "in", "k", "s", "pad"}             Caffe ceil mode
    {"op": "add", "name", "ins", "relu"}
    {"op": "concat", "name", "ins"}
    {"op": "gap", "name", "in"}
    {"op": "fc", "name", "in", "oc"}
    {"op": "softmax", "name", "in"}

The first spec is {"op": "input", "name"}.  Everything here is
straightforward ``jax.numpy`` in float32 at the highest matmul precision and
imports nothing of the system under test.  ``bits`` runs the same network
with every weight and activation rounded to a power-of-two per-tensor scale
of that many bits: ``bits=4`` is the lower-precision control that the
correctness check must reject.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5          # batch norm at its inference defaults (mean 0, var 1)


def param_shapes(specs, in_shape) -> dict:
    """{layer name: (weight shape, bias shape)} for every conv and fc."""
    shapes, out = {}, {}
    for sp in specs:
        op = sp["op"]
        if op == "input":
            shapes[sp["name"]] = tuple(in_shape)
            continue
        src = shapes[sp["in"]] if "in" in sp else shapes[sp["ins"][0]]
        n, h, w, c = src
        if op == "conv":
            k, s = sp["k"], sp["s"]
            p = (k - 1) // 2
            shapes[sp["name"]] = (n, (h + 2 * p - k) // s + 1,
                                  (w + 2 * p - k) // s + 1, sp["oc"])
            out[sp["name"]] = ((k, k, c, sp["oc"]), (sp["oc"],))
        elif op == "maxpool":
            k, s, p = sp["k"], sp["s"], sp.get("pad", 0)
            shapes[sp["name"]] = (n, math.ceil((h + 2 * p - k) / s) + 1,
                                  math.ceil((w + 2 * p - k) / s) + 1, c)
        elif op == "concat":
            shapes[sp["name"]] = (n, h, w,
                                  sum(shapes[i][3] for i in sp["ins"]))
        elif op == "gap":
            shapes[sp["name"]] = (n, 1, 1, c)
        elif op == "fc":
            shapes[sp["name"]] = (n, 1, 1, sp["oc"])
            out[sp["name"]] = ((h * w * c, sp["oc"]), (sp["oc"],))
        else:                                   # add, softmax: same shape
            shapes[sp["name"]] = src
    return out


def make_params(specs, in_shape, seed: int) -> dict:
    """He-normal weights and small biases for every conv and fc, made on the
    device in one jitted call from ``seed``: {name: {"w", "b"}} float32."""
    shapes = param_shapes(specs, in_shape)
    names = sorted(shapes)
    sizes = [int(np.prod(s)) for nm in names for s in shapes[nm]]
    key = jax.random.key(np.random.SeedSequence(seed).generate_state(1)[0])

    @jax.jit
    def make(key):
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, off = {}, 0
        for nm in names:
            ws, bs = shapes[nm]
            fan_in = int(np.prod(ws[:-1]))
            nw, nb = int(np.prod(ws)), int(np.prod(bs))
            out[nm] = {
                "w": flat[off:off + nw].reshape(ws)
                * np.float32(math.sqrt(2.0 / fan_in)),
                "b": flat[off + nw:off + nw + nb].reshape(bs)
                * np.float32(0.05)}
            off += nw + nb
        return out

    return make(key)


def _pow2_fraction(amax, bits: int):
    """Largest power-of-two fraction that keeps ``amax`` inside ``bits``."""
    qmax = 2.0 ** (bits - 1) - 1
    return jnp.floor(jnp.log2(qmax / jnp.maximum(amax, 1e-12)))


def _fake_quant(x, f, bits: int):
    lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x * 2.0 ** f), lo, hi) * 2.0 ** -f


def _layer(sp, env, params):
    op = sp["op"]
    if op == "conv":
        k, s = sp["k"], sp["s"]
        p = (k - 1) // 2
        w, b = params[sp["name"]]["w"], params[sp["name"]]["b"]
        y = jax.lax.conv_general_dilated(
            env[sp["in"]], w, (s, s), [(p, p), (p, p)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
        if sp.get("bn"):
            y = y / jnp.sqrt(1.0 + BN_EPS)
    elif op == "maxpool":
        x = env[sp["in"]]
        k, s, p = sp["k"], sp["s"], sp.get("pad", 0)
        h, w = x.shape[1:3]
        oh = math.ceil((h + 2 * p - k) / s) + 1
        ow = math.ceil((w + 2 * p - k) / s) + 1
        eh = (oh - 1) * s + k - h - 2 * p
        ew = (ow - 1) * s + k - w - 2 * p
        y = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                  (1, s, s, 1),
                                  ((0, 0), (p, p + eh), (p, p + ew), (0, 0)))
    elif op == "add":
        y = sum(env[i] for i in sp["ins"])
    elif op == "concat":
        y = jnp.concatenate([env[i] for i in sp["ins"]], axis=-1)
    elif op == "gap":
        y = jnp.mean(env[sp["in"]], axis=(1, 2), keepdims=True)
    elif op == "fc":
        x = env[sp["in"]]
        w, b = params[sp["name"]]["w"], params[sp["name"]]["b"]
        y = (x.reshape(x.shape[0], -1) @ w + b).reshape(x.shape[0], 1, 1, -1)
    elif op == "softmax":
        y = jax.nn.softmax(env[sp["in"]], axis=-1)
    else:
        raise ValueError(f"reference: unknown op {op!r}")
    if sp.get("relu"):
        y = jnp.maximum(y, 0.0)
    return y


def forward(specs, params, x, *, bits: int | None = None, calib=None):
    """Float32 logits (or probabilities, where the net ends in softmax) of
    ``x`` (N, H, W, 3) float32.

    With ``bits``, weights are rounded per tensor, and every activation is
    rounded to a scale that covers its range over ``calib`` (a float batch
    of the same distribution): the network computed in that precision."""
    with jax.default_matmul_precision("highest"):
        if bits is None:
            return _run(specs, params, x, None, None)
        qparams = {nm: {"w": _fake_quant(p["w"], _pow2_fraction(
            jnp.max(jnp.abs(p["w"])), bits), bits), "b": p["b"]}
            for nm, p in params.items()}
        fracs = _fractions(specs, params, calib, bits)
        return _run(specs, qparams, x, fracs, bits)


def activations(specs, params, x) -> dict:
    """{layer name: float32 output} of every layer for ``x``."""
    with jax.default_matmul_precision("highest"):
        env = {}
        for sp in specs:
            env[sp["name"]] = x if sp["op"] == "input" \
                else _layer(sp, env, params)
        return env


def _run(specs, params, x, fracs, bits):
    env = {}
    for sp in specs:
        y = x if sp["op"] == "input" else _layer(sp, env, params)
        if fracs is not None and sp["op"] != "softmax":
            y = _fake_quant(y, fracs[sp["name"]], bits)
        env[sp["name"]] = y
    return env[specs[-1]["name"]]


def _fractions(specs, params, calib, bits) -> dict:
    env, fracs = {}, {}
    for sp in specs:
        y = calib if sp["op"] == "input" else _layer(sp, env, params)
        env[sp["name"]] = y
        fracs[sp["name"]] = _pow2_fraction(jnp.max(jnp.abs(y)), bits)
    return fracs
