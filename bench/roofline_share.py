"""A kernel kind's share of its roofline, for the metrics of that name."""
from bench import roofline, tracing


def share(run, kind: str):
    """100 * sum of roofline times / sum of device times of ``kind``'s
    launches, over the program executions whole in the trace; None where
    the trace holds none or the padded batch size is not one fixed size."""
    if not run.trace or not run.batch or not run.peak:
        return None
    runs = tracing.complete_runs(run.trace["ops"], run.trace["modules"])
    dev = tracing.kernel_time(runs, kind)
    if dev <= 0:
        return None
    per_run = sum(roofline.roofline_s(la, run.batch, run.peak)
                  for la in run.launches if la["kind"] == kind)
    return 100.0 * len(runs) * per_run / dev
