"""Conv multiply-accumulates the program's fused launches execute beyond the
model's, in % of the model's: halo rows and columns computed again for each
row and width tile, ragged tiles' slack, and stages upstream of a chain's
final conv run once per OC tile (the executor's ``executor.conv_macs_*``
gauges, from the kernel's own tile geometry).  None where the program sets
no such gauge."""
LAYER = "fusion search and lowering (core/pathsearch.py, core/lower.py)"
UNIT = "%"


def read(run, registry=None):
    if registry is None:
        from repro.obs.metrics import REGISTRY as registry
    executed = registry.get("executor.conv_macs_executed")
    model = registry.get("executor.conv_macs_model")
    if executed is None or model is None or not model.value:
        return None
    return 100.0 * (executed.value - model.value) / model.value
