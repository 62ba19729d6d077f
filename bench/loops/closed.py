"""Closed loop (MLPerf Offline): ``outstanding`` single-image requests are
kept in flight; each completion sends the next.  A request is due when it
is sent."""
import threading
import time

from bench.traffic import DRAIN_S


def drive(mix, seed, clock, send):
    slots = threading.Semaphore(mix["outstanding"])
    while slots.acquire(timeout=DRAIN_S):
        now = time.perf_counter()
        if now >= clock.end:
            break
        send(now).add_done_callback(lambda f: slots.release())
