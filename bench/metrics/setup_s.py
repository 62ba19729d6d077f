"""Process start to the window's open: imports, weights and images,
quantize, search, lower, trace and compile of the batch shapes, the mix's
warm-up (host clock)."""
UNIT = "s"


def read(run):
    return run.setup_s
