"""Mean host time per batch of ``Session.run_batch``'s ``pad`` span (stack
the requests and pad to the allowed size), over the traced seconds."""
LAYER = "session (runtime/session.py)"
UNIT = "ms"


def read(run):
    return sum(run.pad_s) / len(run.pad_s) * 1e3 if run.pad_s else None
