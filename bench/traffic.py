"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix names its loop, a module under ``loops/`` found by that name, and
gives the loop's parameters:

* ``"loop": "closed"``: ``outstanding`` single-image requests are kept in
  flight; each completion sends the next (MLPerf Offline).
* ``"loop": "open"``: requests are due on a Poisson schedule at
  ``rate_per_s`` and sent then, whatever the server is doing (MLPerf
  Server).

A loop's ``drive(mix, seed, clock, send)`` sends requests until the window
closes: ``send(due)`` sends the next request, due at ``due`` on
``time.perf_counter``, and returns its future.  Every loop runs ``warmup_s``
of the same traffic before the measured window (set-up), then ``seconds`` of
window.  Requests draw their images round-robin from a seeded pool.  The
generator records, per request: its due, sent and done times on
``time.perf_counter``, its pool image and its result.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

import numpy as np

from bench import spec

DRAIN_S = 60.0        # how long past the window's close a request may finish


@dataclasses.dataclass
class Request:
    idx: int
    image: int
    due: float
    sent: float = math.nan
    done: float | None = None
    output: object = None
    error: str | None = None


@dataclasses.dataclass
class Window:
    start: float
    end: float
    requests: list

    def due_in_window(self) -> list:
        return [r for r in self.requests if self.start <= r.due < self.end]

    def done_in_window(self) -> list:
        return [r for r in self.requests if r.done is not None
                and r.error is None and self.start <= r.done < self.end]


@dataclasses.dataclass
class Clock:
    """The run's times on ``time.perf_counter``: traffic begins (warm-up),
    the window opens and closes."""
    begin: float
    start: float
    end: float


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the first) of ``rate * seconds`` arrivals: the
    exponential quantiles as gaps, in an order drawn from ``seed``.  Every
    seed gets the same set of gaps, so seeds change the order of the work
    and not its amount."""
    n = int(math.ceil(rate * seconds)) + 1
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))


def run(submit, n_images: int, mix: dict, seconds: float, seed: int,
        on_window=None) -> Window:
    """Drive ``submit(image_index) -> Future`` with ``mix`` for its warm-up
    and ``seconds`` of window; ``on_window(start, end)`` is called as the
    first request due in the window is sent.  Returns once every request
    sent has finished or ``DRAIN_S`` has passed since the close."""
    lock = threading.Lock()
    reqs: list[Request] = []
    begin = time.perf_counter()
    clock = Clock(begin, begin + mix["warmup_s"],
                  begin + mix["warmup_s"] + seconds)
    opened = []

    def finish(req, fut):
        req.done = time.perf_counter()
        try:
            req.output = fut.result()
        except Exception as e:  # noqa: BLE001 -- recorded, judged by check
            req.error = f"{type(e).__name__}: {e}"

    def send(due: float):
        if not opened and due >= clock.start:
            opened.append(due)
            if on_window:
                on_window(clock.start, clock.end)
        with lock:
            req = Request(len(reqs), len(reqs) % n_images, due)
            reqs.append(req)
        req.sent = time.perf_counter()
        fut = submit(req.image)
        fut.add_done_callback(lambda f: finish(req, f))
        return fut

    spec.loop(mix["loop"]).drive(mix, seed, clock, send)
    deadline = max(clock.end, time.perf_counter()) + DRAIN_S
    while time.perf_counter() < deadline:
        with lock:
            if all(r.done is not None for r in reqs):
                break
        time.sleep(0.01)
    with lock:
        return Window(clock.start, clock.end, list(reqs))
