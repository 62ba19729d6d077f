"""Share of the window's batches that the batcher dispatched while an
earlier batch was still uncollected on the device (observer records'
``inflight_at_dispatch`` >= 1), in %.  None where the records lack the
field."""
LAYER = "front door (runtime/batching.py, runtime/server.py)"
UNIT = "%"


def read(run):
    inflight = {r["batch_id"]: r.get("inflight_at_dispatch")
                for r in run.records if r["status"] == "ok"}
    if not inflight or None in inflight.values():
        return None
    return 100 * sum(1 for v in inflight.values() if v >= 1) / len(inflight)
