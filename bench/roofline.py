"""Operations, bytes and roofline time of one fused launch, from its shapes.

A launch is described by plain shapes, per image (batch excluded):

    {"kind": "chain" | "horizontal",
     "in": (H, W, C), "sides": [(H, W, C), ...], "out": (H, W, C),
     "convs": [(kh, kw, ic, oc, oh, ow), ...]}

Operations count two per int8 multiply-accumulate of the convolutions (an fc
is a 1x1 convolution over its flattened input); pooling and adds are left
out, so the count is a floor.  Bytes are what the launch must move through
HBM at least: its int8 input, side inputs and output once per image, and its
int8 weights and int32 biases once per launch.
"""
from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device missing from ``peaks.json`` is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def ops(launch: dict, batch: int) -> int:
    return batch * sum(2 * kh * kw * ic * oc * oh * ow
                       for kh, kw, ic, oc, oh, ow in launch["convs"])


def bytes_moved(launch: dict, batch: int) -> int:
    act = (math.prod(launch["in"]) + math.prod(launch["out"])
           + sum(math.prod(s) for s in launch.get("sides", ())))
    par = sum(kh * kw * ic * oc + 4 * oc
              for kh, kw, ic, oc, _, _ in launch["convs"])
    return batch * act + par


def roofline_s(launch: dict, batch: int, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(ops(launch, batch) / peak["int8_ops_per_s"],
               bytes_moved(launch, batch) / peak["hbm_bytes_per_s"])


def model_ops(specs, in_shape) -> int:
    """Two per multiply-accumulate of every conv and fc of a reference
    network (``reference`` layer specs), per image."""
    from bench.reference import param_shapes

    shapes = param_shapes(specs, (1,) + tuple(in_shape))
    out = _out_hw(specs, in_shape)
    total = 0
    for sp in specs:
        if sp["op"] in ("conv", "fc"):
            w, _ = shapes[sp["name"]]
            oh, ow = out[sp["name"]]
            total += 2 * math.prod(w) * oh * ow
    return total


def _out_hw(specs, in_shape) -> dict:
    hw = {}
    for sp in specs:
        if sp["op"] == "input":
            hw[sp["name"]] = tuple(in_shape[:2])
            continue
        h, w = hw[sp["in"] if "in" in sp else sp["ins"][0]]
        if sp["op"] == "conv":
            k, s = sp["k"], sp["s"]
            p = (k - 1) // 2
            hw[sp["name"]] = ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1)
        elif sp["op"] == "maxpool":
            k, s, p = sp["k"], sp["s"], sp.get("pad", 0)
            hw[sp["name"]] = (math.ceil((h + 2 * p - k) / s) + 1,
                              math.ceil((w + 2 * p - k) / s) + 1)
        elif sp["op"] in ("gap", "fc"):
            hw[sp["name"]] = (1, 1)
        else:
            hw[sp["name"]] = (h, w)
    return hw
