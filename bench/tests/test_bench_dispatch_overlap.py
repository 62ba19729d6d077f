"""``dispatch_overlap_share`` on hand-built observer records, and nothing
where the records lack ``inflight_at_dispatch``."""
import types

import pytest

from bench import spec


def _records(inflight):
    """Four requests per batch; batch i dispatched with ``inflight[i]``
    earlier batches uncollected (None: the field is absent)."""
    recs = []
    for b, n in enumerate(inflight):
        for _ in range(4):
            rec = {"batch_id": b, "batch_size": 4, "status": "ok"}
            if n is not None:
                rec["inflight_at_dispatch"] = n
            recs.append(rec)
    return recs


@pytest.mark.parametrize("inflight, want", [
    ([0, 1, 1, 1], 75.0),
    ([0, 0], 0.0),
    ([1, 1, 1], 100.0),
    ([None, None], None),
    ([], None),
])
def test_dispatch_overlap_share(inflight, want):
    run = types.SimpleNamespace(records=_records(inflight))
    got = spec.metric("dispatch_overlap_share").read(run)
    assert got == (pytest.approx(want) if want is not None else None)
