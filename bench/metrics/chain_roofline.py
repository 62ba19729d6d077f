"""Roofline time of the chain launches over their device time, across the
program executions that lie whole in the trace (``roofline.py``)."""
from bench.roofline_share import share

LAYER = "kernels (kernels/conv_fused)"
UNIT = "%"


def read(run):
    return share(run, "chain")
