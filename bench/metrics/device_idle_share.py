"""Share of the traced window in which no operation ran on the device
(profiler trace)."""
from bench import tracing

LAYER = "device"
UNIT = "%"


def read(run):
    t = run.trace
    if not t or not t["ops"]:
        return None
    return 100.0 * (1.0 - tracing.busy_s(t["ops"]) / (t["t1"] - t["t0"]))
