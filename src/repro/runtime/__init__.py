"""Runtime supporter (paper §1, §3.2): serve compiled artifacts end to end.

DNNVM is "an integration of optimizers ..., an assembler, a runtime supporter
and a validation environment"; this package is the runtime supporter — the
host-side layer that feeds the accelerator:

* :class:`Session`         — owns one compiled model (artifact via PlanCache,
                             executor, memory plan); ``run`` / ``run_batch``
                             / ``launch`` (dispatch without waiting).
* :class:`DynamicBatcher`  — async request queue with max-batch / max-latency
                             knobs; a worker dispatches queued images as one
                             batched launch while the previous batch runs,
                             a collector resolves each batch's futures.
* :class:`Server`          — Session + batcher + latency/batch metrics.
* :class:`MultiServer`     — many models on one device: DDR partitioning,
                             per-tenant SLO classes, admission control.
* :class:`Fleet`           — N data-parallel Session replicas across
                             ``jax.devices()``: health-driven failover,
                             bounded retries, elastic re-admission.
* :class:`ChaosInjector`   — deterministic fault injection (kill / poison /
                             hang / slow) on fleet replicas, for the chaos
                             gate.
* :func:`pipeline_report`  — engine-level cross-request schedule: the
                             artifact's addressed instruction stream,
                             software-pipelined across requests on the time
                             wheel and audited by the memory-hazard oracle.
"""
from repro.runtime.batching import BatcherClosed, DynamicBatcher
from repro.runtime.chaos import ChaosError, ChaosInjector
from repro.runtime.fleet import (DeadlineExceeded, Fleet, FleetError,
                                 RetriesExhausted)
from repro.runtime.multitenant import (SLO_CLASSES, AdmissionError,
                                       MultiServer)
from repro.runtime.schedule import (PipelineReport, pipeline_report,
                                    pipeline_stream)
from repro.runtime.server import Server
from repro.runtime.session import Session

__all__ = ["AdmissionError", "BatcherClosed", "ChaosError", "ChaosInjector",
           "DeadlineExceeded", "DynamicBatcher", "Fleet", "FleetError",
           "MultiServer", "PipelineReport", "RetriesExhausted", "SLO_CLASSES",
           "Server", "Session", "pipeline_report", "pipeline_stream"]
