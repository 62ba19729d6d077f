"""The system under test for one cell, and the inputs it is fed.

Weights and images come from the seed and from this directory's reference:
the reference's layer specs give every weight's shape, ``reference.
make_params`` draws them on the device in one call, and the program is handed
the same float weights to quantize as a user would hand it trained ones.
Images are int8 at the configuration's ``input_fraction`` (real value
q * 2**-f); the model is quantized on a float batch of the same images.
"""
from __future__ import annotations

import numpy as np


def images(cfg: dict, n: int, seed: int) -> np.ndarray:
    """(n, H, W, C) int8 images at ``input_fraction``: standard normal pixels
    under a per-image contrast and colour cast (``cfg["images"]``), rounded
    and saturated.  Images that differ in their global statistics, as
    photographs do, get answers that differ; on pure noise a deep random
    network gives every image nearly the same answer."""
    rng = np.random.default_rng([seed, 0])
    s, c, im = cfg["image_size"], cfg["channels"], cfg["images"]
    x = rng.standard_normal((n, s, s, c), np.float32)
    x *= rng.uniform(*im["contrast"], (n, 1, 1, 1)).astype(np.float32)
    x += rng.normal(0.0, im["cast_std"], (n, 1, 1, c)).astype(np.float32)
    x *= np.float32(2.0 ** cfg["input_fraction"])
    return np.clip(np.rint(x), -128, 127).astype(np.int8)


def as_float(cfg: dict, q: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * np.float32(2.0 ** -cfg["input_fraction"])


def graph(cfg: dict):
    """The program's graph of the configuration's network."""
    from repro.cnn import build

    kw = {} if cfg["softmax"] else {"softmax": False}
    return build(cfg["net"], img=cfg["image_size"],
                 num_classes=cfg["num_classes"], **kw)


def session(cfg: dict, specs, params, calib: np.ndarray):
    """Quantize (``bench/quantize.py``), plan for the configuration's device
    model, lower, and open a ``Session`` on the fused Pallas backend."""
    from repro import hw
    from repro.core import pathsearch
    from repro.runtime import Session

    from bench.quantize import quantize

    g = graph(cfg)
    qm = quantize(g, specs, params, calib)
    dev = getattr(hw, cfg["plan_for"])
    return Session(g, pathsearch.search(g, dev), dev, qm, backend="pallas")


def served_values(sess, outputs: list) -> tuple:
    """(rows, probs): the served outputs as float rows, and whether they are
    probabilities.  int8 outputs are read at the fraction the program states
    for its output tensor."""
    name = sess.outputs[-1]
    y = np.concatenate([np.asarray(o[name]).reshape(1, -1) for o in outputs])
    if y.dtype == np.int8:
        return y.astype(np.float64) * 2.0 ** -sess.qm.f_a[name], False
    return y.astype(np.float64), True


def launches(g, program) -> list:
    """The fused launches of a lowered program of graph ``g`` as plain
    shapes per image (``roofline`` format)."""
    from repro.core.lower import FusedLaunch

    hwc = lambda t: tuple(g.shape(t)[1:])               # noqa: E731
    out = []
    for it in program.items:
        if not isinstance(it, FusedLaunch):
            continue
        if it.kind == "horizontal":
            kh, kw = it.kernel
            ic = g.shape(it.in_name)[3]
            oh, ow = it.out_hw
            convs = [(kh, kw, ic, oc, oh, ow) for _, oc, _, _ in it.members]
            out.append({"kind": "horizontal", "in": hwc(it.in_name),
                        "sides": [], "convs": convs,
                        "out": (oh, ow, sum(c[3] for c in convs))})
            continue
        convs = []
        for st in it.stages:
            if st[0] != "conv":
                continue
            node = g.nodes[st[1]]
            ic_shape = g.shape(node.inputs[0])
            if node.op == "fc":
                convs.append((1, 1, int(np.prod(ic_shape[1:])),
                              node.attrs["oc"], 1, 1))
            else:
                convs.append((st[2], st[3], ic_shape[3], node.attrs["oc"],
                              st[12], st[13]))
        out.append({"kind": "chain", "in": hwc(it.in_name),
                    "sides": [hwc(s) for s in it.sides], "convs": convs,
                    "out": hwc(it.out_name)})
    return out
