"""Async dynamic-batching front end for the runtime supporter.

Requests arrive one image at a time; the accelerator is happiest launching
once per *batch* (one Pallas grid covers all N images).  The
:class:`DynamicBatcher` sits between the two: ``submit`` enqueues a request
and returns a future immediately, a worker drains the queue into batches
bounded by two knobs —

* ``max_batch``     — never launch more than this many images at once;
* ``max_latency_s`` — while no earlier batch is uncollected, never hold the
  *oldest* queued request longer than this before flushing a partial batch.
  While one is, a partial batch is held until it fills or that batch
  completes: the device is busy, so an early flush would only queue a
  padded batch behind it, and no request waits longer than it would have
  waited for the batch in flight to end.

Two threads serve.  The worker forms batches and owns every executor
dispatch (JAX dispatch stays on one thread); the collector waits for each
batch's results, resolves its futures in submit order and runs the
observers.  A runner that returns a pending batch (``Session.launch``) lets
the worker stack, copy in and dispatch the next batch while the device
still runs the last one; at most two batches are uncollected at once, one
running and one queued behind it on the device.  Completion is delivered
through ``concurrent.futures.Future``, so callers can block, poll, or chain
callbacks.  ``close()`` drains the queued requests, then the batches in
flight, and joins both threads; submitting after close raises
:class:`BatcherClosed`.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from concurrent.futures import Future

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import DEFAULT_BATCH_BUCKETS

# dispatched batches whose results are not yet collected: one running on the
# device and one queued behind it, so the device never waits on the host
_MAX_UNCOLLECTED = 2


class BatcherClosed(RuntimeError):
    """submit() after close()."""


class DynamicBatcher:
    def __init__(self, run_batch, *, max_batch: int = 8,
                 max_latency_s: float = 2e-3, clock=time.monotonic,
                 latency_window: int = 16384, registry=None, tracer=None,
                 labels: dict | None = None, observers=None):
        """``run_batch(xs)`` executes one batch and returns either the list
        of results (one per request, same order) or a pending batch: an
        object with a ``results()`` method that waits for the device and
        returns that list.  The batcher tells them apart by that method; a
        list is complete when handed over, a pending batch counts as
        uncollected until the collector's ``results()`` returns.
        ``latency_window`` bounds the retained latency samples (a
        long-running server must not grow without bound).

        Besides end-to-end ``latencies`` (submit -> result), the batcher keeps
        ``queue_waits`` (submit -> batch formed, per request) and
        ``execute_s`` (batch formed -> results back, per batch) so an SLO
        controller can tell a queue-bound p99 violation from a launch-bound
        one.  Under back-to-back batches ``execute_s`` includes the time a
        batch queued on the device behind the one before it.  The collector
        appends to these under the batcher's lock; read them through
        :meth:`samples`.  When the shared tracer is enabled, each request gets
        a queue-wait + execute track and each batch a batch-track span.
        ``labels`` tags every emitted metric (multi-tenant serving labels
        per-model: ``serve.requests{model=vgg16}``).

        ``observers`` are callables invoked on the collector thread once per
        request after its batch completes (and on batch failure), with one
        record dict: ``req_id``, ``submit_s``, ``queue_wait_s``,
        ``execute_s``, ``latency_s``, ``batch_id``, ``batch_size``,
        ``batch_members``, ``status`` ("ok" | "error"), ``error`` and
        ``inflight_at_dispatch`` (earlier batches still uncollected when
        this one was dispatched: 0 or 1).  The flight recorder and the SLO
        burn-rate tracker plug in here; observer exceptions are swallowed —
        observability must not break serving."""
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self._run_batch = run_batch
        self.max_batch = max_batch
        self.max_latency_s = max_latency_s
        self._clock = clock
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._closed = False
        self._seq = 0                    # request sequence id (trace tracks)
        self._n_batches = 0
        self._uncollected = 0            # dispatched pending batches
        # worker -> collector, in dispatch order
        self._handoff: queue.Queue = queue.Queue(maxsize=_MAX_UNCOLLECTED)
        self.batch_sizes: collections.Counter = collections.Counter()
        self.n_served = 0
        # submit -> result per request, most recent latency_window samples;
        # recorded BEFORE the future resolves, so a caller reading stats
        # right after result() returns never sees a partial sample set
        self.latencies: collections.deque = collections.deque(
            maxlen=latency_window)
        # submit -> batch formation, per request (same window discipline)
        self.queue_waits: collections.deque = collections.deque(
            maxlen=latency_window)
        # batch formation -> results back, per BATCH
        self.execute_s: collections.deque = collections.deque(
            maxlen=latency_window)
        self._registry = (registry if registry is not None
                          else obs_metrics.REGISTRY)
        self._tracer = tracer if tracer is not None else obs_trace.TRACER
        self.labels = dict(labels) if labels else None
        self._observers = list(observers) if observers else []
        self._m_requests = self._registry.counter("serve.requests", self.labels)
        self._m_batches = self._registry.counter("serve.batches", self.labels)
        self._m_overlapped = self._registry.counter("serve.batches_overlapped",
                                                    self.labels)
        self._m_errors = self._registry.counter("serve.errors", self.labels)
        self._m_depth = self._registry.gauge("serve.queue_depth", self.labels)
        self._m_batch = self._registry.histogram("serve.batch_size",
                                                 DEFAULT_BATCH_BUCKETS,
                                                 labels=self.labels)
        self._m_latency = self._registry.histogram("serve.latency_ms",
                                                   labels=self.labels)
        self._m_wait = self._registry.histogram("serve.queue_wait_ms",
                                                labels=self.labels)
        self._m_exec = self._registry.histogram("serve.execute_ms",
                                                labels=self.labels)
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="dnnvm-batcher")
        self._collector = threading.Thread(target=self._collect_loop,
                                           daemon=True,
                                           name="dnnvm-batcher-collect")
        self._worker.start()
        self._collector.start()

    # --------------------------------------------------------------- client
    def submit(self, x) -> Future:
        fut: Future = Future()
        with self._cv:
            if self._closed:
                raise BatcherClosed("batcher is closed")
            self._seq += 1
            self._queue.append((x, fut, self._clock(), self._seq))
            n = len(self._queue)
            self._m_depth.set(n)
            # the worker's decision changes only when the queue starts (its
            # deadline runs from the oldest request) or fills
            if n == 1 or n >= self.max_batch:
                self._cv.notify_all()
        self._m_requests.inc()
        return fut

    def set_max_batch(self, n: int) -> None:
        """Retarget the batch-size cap (latency-SLO-aware serving shrinks and
        regrows it at run time).  Takes effect for the next formed batch; the
        worker is woken in case the queue already satisfies the new cap."""
        if n < 1:
            raise ValueError("max_batch must be >= 1")
        with self._cv:
            self.max_batch = n
            self._cv.notify_all()

    def samples(self) -> dict:
        """Copies of ``latencies``, ``queue_waits``, ``execute_s``,
        ``batch_sizes`` and ``n_served``, taken under the lock the collector
        updates them under."""
        with self._lock:
            return {"latencies": list(self.latencies),
                    "queue_waits": list(self.queue_waits),
                    "execute_s": list(self.execute_s),
                    "batch_sizes": dict(self.batch_sizes),
                    "n_served": self.n_served}

    def close(self, wait: bool = True, timeout_s: float | None = None) -> None:
        """Flush whatever is queued, collect the batches in flight, then stop
        both threads.  Idempotent; with an empty queue this returns as soon
        as the threads observe the flag.  ``timeout_s`` bounds the joins
        together (the fleet closes possibly-wedged replicas without hanging
        its own shutdown)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if wait:
            t_end = None if timeout_s is None else time.monotonic() + timeout_s
            self._worker.join(timeout=timeout_s)
            self._collector.join(
                timeout=None if t_end is None
                else max(0.0, t_end - time.monotonic()))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    # --------------------------------------------------------------- worker
    def _form(self):
        """Wait until a batch is due and pop it; None once closed and
        drained.  A batch is due when a dispatch slot is free and the queue
        fills it, or at shutdown, or when nothing is uncollected and the
        OLDEST request has waited max_latency_s since submit."""
        with self._cv:
            while True:
                if not self._queue:
                    if self._closed:
                        return None
                    self._cv.wait()
                elif self._uncollected >= _MAX_UNCOLLECTED:
                    self._cv.wait()
                elif len(self._queue) >= self.max_batch or self._closed:
                    break
                elif self._uncollected:
                    self._cv.wait()      # hold: woken when it fills or drains
                else:
                    remaining = (self._queue[0][2] + self.max_latency_s
                                 - self._clock())
                    if remaining <= 0:
                        break
                    self._cv.wait(timeout=remaining)
            batch = [self._queue.popleft()
                     for _ in range(min(self.max_batch, len(self._queue)))]
            self._m_depth.set(len(self._queue))
            return batch

    def _loop(self) -> None:
        while (batch := self._form()) is not None:
            self._dispatch(batch)
        self._handoff.put(None)          # the collector's stop mark

    def _dispatch(self, batch) -> None:
        """Run the batch's launch phase and hand it to the collector as
        (batch, t_form, inflight_at_dispatch, output, pending, error)."""
        t_form = self._clock()
        try:
            out = self._run_batch([x for x, _, _, _ in batch])
        except Exception as e:  # fails this batch alone, in submit order
            with self._lock:
                inflight = self._uncollected
            self._handoff.put((batch, t_form, inflight, None, False, e))
            return
        pending = callable(getattr(out, "results", None))
        with self._lock:
            inflight = self._uncollected
            self._uncollected += pending
        self._handoff.put((batch, t_form, inflight, out, pending, None))

    # ------------------------------------------------------------ collector
    def _collect_loop(self) -> None:
        while (item := self._handoff.get()) is not None:
            batch, t_form, inflight, out, pending, error = item
            if pending:
                try:
                    out = out.results()
                except Exception as e:  # a device error surfaces here
                    error = e
                finally:
                    with self._cv:
                        self._uncollected -= 1
                        self._cv.notify_all()
            self._complete(batch, t_form, inflight, out, error)

    def add_observer(self, fn) -> None:
        """Register a per-request completion observer (see ``observers``)."""
        self._observers.append(fn)

    def _notify(self, batch, t_form: float, t_done: float, inflight: int,
                status: str, error: str | None) -> None:
        if not self._observers:
            return
        members = tuple(seq for _, _, _, seq in batch)
        bid = self._n_batches
        for _, _, t0, seq in batch:
            rec = {"req_id": seq, "submit_s": t0,
                   "queue_wait_s": t_form - t0,
                   "execute_s": t_done - t_form,
                   "latency_s": t_done - t0,
                   "batch_id": bid, "batch_size": len(batch),
                   "batch_members": members,
                   "status": status, "error": error,
                   "inflight_at_dispatch": inflight}
            for fn in self._observers:
                try:
                    fn(rec)
                except Exception:    # observers must never break serving
                    pass

    def _complete(self, batch, t_form: float, inflight: int, results,
                  error) -> None:
        t_done = self._clock()
        if error is not None:  # surface the failure on every waiting future
            self._m_errors.inc(len(batch))
            self._notify(batch, t_form, t_done, inflight, "error",
                         f"{type(error).__name__}: {error}")
            for _, fut, _, _ in batch:
                fut.set_exception(error)
            return
        with self._lock:
            self.batch_sizes[len(batch)] += 1
            self.n_served += len(batch)
            self._n_batches += 1
            self.execute_s.append(t_done - t_form)
            for _, _, t0, _ in batch:
                self.queue_waits.append(t_form - t0)
                self.latencies.append(t_done - t0)
        self._m_batches.inc()
        if inflight:
            self._m_overlapped.inc()
        self._m_batch.observe(len(batch))
        self._m_exec.observe((t_done - t_form) * 1e3)
        for _, _, t0, _ in batch:
            self._m_wait.observe((t_form - t0) * 1e3)
            self._m_latency.observe((t_done - t0) * 1e3)
        for (_, fut, _, _), res in zip(batch, results):
            fut.set_result(res)
        self._notify(batch, t_form, t_done, inflight, "ok", None)
        if self._tracer.enabled:
            self._trace_batch(batch, t_form, t_done, self._clock())

    def _trace_batch(self, batch, t_form: float, t_done: float,
                     t_resolved: float) -> None:
        """Emit serve spans for one completed batch: per-request queue-wait +
        execute on a ``req<seq>`` track, plus batch-form / launch / resolve on
        the shared batch track.  Timestamps are the batcher's own clock
        (``time.monotonic`` by default — the tracer's default clock too, so
        these land on the same axis as compile spans)."""
        tr = self._tracer
        bid = self._n_batches
        for _, _, t0, seq in batch:
            track = f"req{seq}"
            tr.add_span("queue_wait", t0, t_form, cat="serve", track=track,
                        args={"batch": bid})
            tr.add_span("execute", t_form, t_done, cat="serve", track=track,
                        args={"batch": bid})
        oldest = min(t0 for _, _, t0, _ in batch)
        tr.add_span("batch_form", oldest, t_form, cat="serve", track="batch",
                    args={"batch": bid, "size": len(batch)})
        tr.add_span("batch_execute", t_form, t_done, cat="serve",
                    track="batch", args={"batch": bid, "size": len(batch)})
        tr.add_span("resolve", t_done, t_resolved, cat="serve", track="batch",
                    args={"batch": bid})
