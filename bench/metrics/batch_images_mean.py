"""Mean images per launch over the batches formed in the window (batcher
observer records)."""
LAYER = "front door (runtime/batching.py, runtime/server.py)"
UNIT = "images"


def read(run):
    sizes = {r["batch_id"]: r["batch_size"] for r in run.records
             if r["status"] == "ok"}
    return sum(sizes.values()) / len(sizes) if sizes else None
