"""Weight and bias megabytes the fused launches pull from HBM per image, at
the mix's one padded batch: the executor's
``executor.weight_bytes_fetched{batch=<n>}`` gauge (a block is fetched again
whenever its block index changes between consecutive grid steps) over the
batch.  None where the program sets no such gauge or the mix pads to no one
batch size."""
LAYER = "kernels (kernels/conv_fused)"
UNIT = "MB"


def read(run, registry=None):
    if registry is None:
        from repro.obs.metrics import REGISTRY as registry
    if not run.batch:
        return None
    fetched = registry.get(f"executor.weight_bytes_fetched{{batch={run.batch}}}")
    if fetched is None:
        return None
    return fetched.value / run.batch / 1e6
