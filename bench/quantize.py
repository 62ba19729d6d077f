"""Offline int8 quantization of a configuration's float weights, handed to
the program as its ``QuantizedModel``.

The program's own ``quantize.calibrate`` gives every pooling node a fraction
of its own, while its int8 max, average and global average pools pass values
through unscaled: where the two fractions differ, the pool's output is read
at twice or half its value (PERF.md, Open questions).  Here the model is
quantized as an offline quantizer hands a compiler its model, by calibrate's
rules with that one change: fractions are the MSE-best power of two (the
range's own, or one either side; the program's ``best_fraction`` for
weights), an eltwise add or a concat takes the least fraction of itself and
its inputs, biases sit at f_in + f_w, and a pooling node keeps its input's
fraction, as its int8 operation assumes.

Activation ranges come from the reference's float32 forward over the
calibration batch (``reference.activations``), reduced to one fraction per
layer on the device, so no activation leaves it; the reference's layer names
are the program graph's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference

POOLS = ("maxpool", "avgpool", "global_avgpool")
F_MIN, F_MAX = -12, 24          # the program's range of fractions


def _best(a, bits: int = 8):
    """MSE-best power-of-two fraction of ``a`` among the range's own and its
    two neighbours (lowest on a tie)."""
    qmax = 2.0 ** (bits - 1) - 1
    f0 = jnp.floor(jnp.log2(qmax / jnp.maximum(jnp.max(jnp.abs(a)), 1e-9)))
    errs = jnp.stack([jnp.mean((jnp.clip(jnp.round(a * 2.0 ** f),
                                         -qmax - 1, qmax) * 2.0 ** -f - a)
                               ** 2) for f in (f0 - 1, f0, f0 + 1)])
    return jnp.clip(f0 - 1 + jnp.argmin(errs), F_MIN, F_MAX)


def _act_fractions(specs):
    @jax.jit
    def go(params, x):
        acts = reference.activations(specs, params, x)
        return {n: _best(a) for n, a in acts.items()}
    return go


def quantize(g, specs, params, calib):
    """The program's ``QuantizedModel`` of graph ``g`` from the reference
    layer ``specs``, float ``params`` ({node: {"w", "b"}}, on the device)
    and the float calibration batch ``calib``."""
    from repro.core.quantize import (QuantizedModel, best_fraction,
                                     fold_conv_intrinsics, quantize_to)

    best = {n: int(v) for n, v in jax.device_get(
        _act_fractions(specs)(params, calib)).items()}
    f_a = {}
    for node in g:
        if node.op in POOLS:
            f_a[node.name] = f_a[node.inputs[0]]
            continue
        f_a[node.name] = best[node.name]
        if node.op in ("concat", "eltwise_add"):
            f_a[node.name] = min([f_a[node.name]]
                                 + [f_a[i] for i in node.inputs])
    host = jax.device_get(params)
    weights, biases, f_w = {}, {}, {}
    for node in g:
        if node.name not in host:
            continue
        w, b = host[node.name]["w"], host[node.name]["b"]
        if node.attrs.get("folded_intrinsics"):
            w, b = fold_conv_intrinsics(w, b, node.attrs["folded_intrinsics"])
        f_w[node.name] = best_fraction(w)
        weights[node.name] = quantize_to(w, f_w[node.name])
        biases[node.name] = quantize_to(
            b, f_a[node.inputs[0]] + f_w[node.name], bits=32)
    return QuantizedModel(weights, biases, f_w, f_a)
