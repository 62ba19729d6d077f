"""Share of the device's busy time in which no fused launch ran: pads,
slices, concatenations, softmax and copies of the executor's program
(profiler trace)."""
from bench import tracing

LAYER = "executor program (core/executor.py, core/lower.py)"
UNIT = "%"


def read(run):
    ops = run.trace["ops"] if run.trace else []
    busy = tracing.busy_s(ops)
    if busy <= 0:
        return None
    return 100.0 * tracing.outside_kernel_s(ops) / busy
