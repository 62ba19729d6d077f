"""Open loop (MLPerf Server): requests are due on a Poisson schedule at
``rate_per_s`` and sent then, whatever the server is doing; a request is
timed from its due time, so a stall delays every request behind it."""
import time

from bench.traffic import poisson_offsets


def drive(mix, seed, clock, send):
    offs = poisson_offsets(mix["rate_per_s"], clock.end - clock.begin, seed)
    for off in offs:
        due = clock.begin + float(off)
        if due >= clock.end:
            break
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        send(due)
