"""The whole step's share of the chip's int8 peak, from the device trace:
the network's int8 operations per program execution (padded batch times
operations per image) over the device's period between successive program
starts, over the peak (``peaks.json``).  The period holds the device's idle
time between programs, so the host's share shows here too."""
from bench import tracing

LAYER = "whole step"
UNIT = "%"


def read(run):
    if not run.trace or not run.batch or not run.peak:
        return None
    period = tracing.program_period_s(run.trace["ops"], run.trace["modules"])
    if period is None:
        return None
    return (100.0 * run.batch * run.ops_per_image
            / (period * run.peak["int8_ops_per_s"]))
