"""The readers of the planner's host-stage spans, on made-up spans: each
sums its own spans in ms per request served."""
import types

import pytest

from harness import spec


def span(i, name, t0, t1, parent=0):
    return types.SimpleNamespace(span_id=i, name=name, t0=t0, t1=t1,
                                 parent_id=parent, attrs={})


# two requests' plans; the second has no climb (a greedy-only request)
SPANS = [
    span(1, "plan", 0.0, 1.0),
    span(2, "overlays", 0.0, 0.05, 1),
    span(3, "bucket_launch", 0.05, 0.2, 1),
    span(4, "bucket_rows", 0.05, 0.07, 3),
    span(5, "ls_climb", 0.2, 0.8, 1),
    span(6, "ls_prep", 0.2, 0.21, 5),
    span(7, "ls_device_climb", 0.21, 0.6, 5),
    span(8, "assemble", 0.8, 0.95, 1),
    span(9, "validate", 0.8, 0.83, 8),
    span(10, "validate", 0.83, 0.87, 8),
    span(11, "plan", 2.0, 2.5),
    span(12, "overlays", 2.0, 2.03, 11),
    span(13, "bucket_launch", 2.03, 2.2, 11),
    span(14, "bucket_rows", 2.03, 2.04, 13),
    span(15, "assemble", 2.2, 2.3, 11),
    span(16, "validate", 2.2, 2.25, 15),
]


@pytest.mark.parametrize("metric, stage, stage_s", [
    ("overlays_ms.batch", "overlays", 0.05 + 0.03),
    ("greedy_rows_ms.batch", "bucket_rows", 0.02 + 0.01),
    ("assemble_ms.batch", "assemble", 0.15 + 0.1),
    ("validate_ms.batch", "validate", 0.03 + 0.04 + 0.05),
    ("ls_prep_ms.batch", "ls_prep", 0.01),
])
def test_a_stage_reader_is_its_spans_in_ms_per_request_served(
        metric, stage, stage_s):
    read = spec.reader(metric)
    served = [types.SimpleNamespace(ok=ok) for ok in (True, True, False)]
    run = types.SimpleNamespace(spans=SPANS, records=served)
    assert read(run) == pytest.approx(1e3 * stage_s / 2)
    # a program without the span, or a window that served nothing, reads
    # nothing
    run.spans = [s for s in SPANS if s.name != stage]
    assert read(run) is None
    run.spans, run.records = SPANS, served[2:]
    assert read(run) is None
