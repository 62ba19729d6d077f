"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests finished in it, drawn
from the seed, is run through the configuration's plain float32 reference
(``reference.py``) on the same images and weights.  Each served output is
turned into centred logits: an int8 output at the fraction the program
states for it is dequantized, a probability vector is taken to its log
(floored at the smallest normal float32), and the mean over classes is
subtracted, which leaves the classes' order and spacing and nothing a
softmax ignores.  Two numbers are compared:

* ``logit_rel_err_max``: the widest relative gap over the sample,
  ||served - reference|| / ||reference||;
* ``departure_err_max``: the widest gap, over the sample, between a served
  answer's departure from the sample's mean served answer and its
  reference's departure from the sample's mean reference, over the size of
  that departure or of the sample's median departure, whichever is larger.
  Answers differ from image to image by much less than they share, so an
  answer served to the wrong request moves the first number little; its
  departure is another image's, and reads about 1.4 here or more.

A request due in the window that errored or never finished fails the run
by itself.
"""
from __future__ import annotations

import numpy as np

SAMPLE = 64            # requests compared per run
BLOCK = 32             # reference batch: one compiled shape
FLOOR = float(np.finfo(np.float32).tiny)


def centred(y: np.ndarray, probs: bool) -> np.ndarray:
    y = np.asarray(y, np.float64).reshape(len(y), -1)
    if probs:
        y = np.log(np.maximum(y, FLOOR))
    return y - y.mean(axis=1, keepdims=True)


def rel_gaps(served, ref, probs: bool) -> np.ndarray:
    """Per row ||centred(served) - centred(ref)|| / ||centred(ref)||."""
    a, b = centred(served, probs), centred(ref, probs)
    return np.linalg.norm(a - b, axis=1) / np.linalg.norm(b, axis=1)


def departure_gaps(served, ref, probs: bool) -> np.ndarray:
    """Per row, ||(a - mean(a)) - (b - mean(b))|| / max(||b - mean(b)||,
    median ||b - mean(b)||), with ``a`` and ``b`` the centred served and
    reference rows and the means over the sample's rows; needs two rows or
    more."""
    a, b = centred(served, probs), centred(ref, probs)
    if len(a) < 2:
        raise ValueError("departures need two rows or more")
    a = a - a.mean(axis=0, keepdims=True)
    b = b - b.mean(axis=0, keepdims=True)
    size = np.linalg.norm(b, axis=1)
    return np.linalg.norm(a - b, axis=1) / np.maximum(size, np.median(size))


def numbers(served, ref, probs: bool) -> dict:
    """{name: value} of the numbers compared over one sample."""
    return {"logit_rel_err_max": float(rel_gaps(served, ref, probs).max()),
            "departure_err_max": float(
                departure_gaps(served, ref, probs).max())}


def sample(n_done: int, seed: int, k: int = SAMPLE) -> np.ndarray:
    """Indices of the requests to compare, drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    return np.sort(rng.choice(n_done, size=min(k, n_done), replace=False))


def reference_outputs(forward, images: np.ndarray) -> np.ndarray:
    """``forward(block)`` over ``images`` in blocks of ``BLOCK`` rows, the
    last one padded, so one program serves every block."""
    out = []
    for i in range(0, len(images), BLOCK):
        blk = images[i:i + BLOCK]
        n = len(blk)
        if n < BLOCK:
            blk = np.concatenate(
                [blk, np.zeros((BLOCK - n,) + blk.shape[1:], blk.dtype)])
        out.append(np.asarray(forward(blk))[:n])
    return np.concatenate(out)


def verdict(numbers: dict) -> bool:
    """``numbers``: {name: {"value", "limit"}}; every value within its
    limit.  A number that could not be read (None) fails."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in numbers.values())
