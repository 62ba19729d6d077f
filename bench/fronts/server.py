"""The program's ``Server``: one ``Session`` behind its dynamic batcher,
with the mix's ``max_batch``, ``allowed_sizes`` and ``max_latency_s``."""


def build(sess, params: dict, observers: list):
    from repro.runtime import Server

    return Server(sess, max_batch=params["max_batch"],
                  allowed_sizes=params["allowed_sizes"],
                  max_latency_s=params["max_latency_s"],
                  observers=observers)


def padded_batch(params: dict):
    """The one batch size every launch is padded to, or None."""
    sizes = params["allowed_sizes"]
    return sizes[0] if len(sizes) == 1 else None


def batches(front) -> dict:
    """{batch size: batches formed} so far."""
    return dict(front._batcher.batch_sizes)
