"""The readers of the per-layer metrics that read the program's own span
tree (``ingest``, ``dedup``, the step's ``put`` / ``dispatch`` / ``pull``,
``detections``) on hand-built span tables: each divides by pushes or by
executions of the step, and each returns None where the program has no
such span (a program older than the span tree)."""
import pytest

from bench import harness

# name -> (count, total_s) of one window, as the harness builds it: the
# program's span totals plus its own "push" and "poll"
SPANS = {"push": (100, 35.0), "poll": (100, 0.031),
         "chunk": (100, 34.9), "ingest": (200, 2.5), "dedup": (100, 2.0),
         "fused_step": (80, 26.0), "put": (80, 0.04), "dispatch": (80, 0.4),
         "wait": (80, 25.5), "pull": (80, 0.016), "host_tail": (80, 0.06),
         "detections": (100, 0.033)}

EXPECT = {
    "ingest_span_ms.backfill": 2.5 / 100 * 1e3,    # per push
    "dedup_ms.backfill": 2.0 / 100 * 1e3,          # per push
    "detections_ms.backfill": 0.033 / 100 * 1e3,   # per push
    "step_put_ms.backfill": 0.04 / 80 * 1e3,       # per step execution
    "step_dispatch_ms.backfill": 0.4 / 80 * 1e3,   # per step execution
    "step_pull_ms.backfill": 0.016 / 80 * 1e3,     # per step execution
}
SPAN_OF = {"ingest_span_ms.backfill": "ingest",
           "dedup_ms.backfill": "dedup",
           "detections_ms.backfill": "detections",
           "step_put_ms.backfill": "put",
           "step_dispatch_ms.backfill": "dispatch",
           "step_pull_ms.backfill": "pull"}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_value(name):
    assert harness.reader(name)({"spans": dict(SPANS)}) == \
        pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_none_without_its_span(name):
    spans = {k: v for k, v in SPANS.items() if k != SPAN_OF[name]}
    assert harness.reader(name)({"spans": spans}) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_none_on_an_empty_window(name):
    spans = {k: (0, 0.0) for k in SPANS}
    assert harness.reader(name)({"spans": spans}) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_none_on_a_program_before_the_span_tree(name):
    """A span table from before the tree: no ``chunk`` root, and an
    ``ingest`` span that enclosed the step, which is not the ingest
    layer alone."""
    spans = {"push": (100, 35.0), "poll": (100, 0.031),
             "ingest": (100, 34.9), "fused_step": (100, 32.4),
             "host_tail": (100, 0.06)}
    assert harness.reader(name)({"spans": spans}) is None


def test_step_readers_divide_by_executions_not_pushes():
    spans = dict(SPANS, push=(160, 35.0))
    for name in ("step_put_ms.backfill", "step_dispatch_ms.backfill",
                 "step_pull_ms.backfill"):
        assert harness.reader(name)({"spans": spans}) == \
            pytest.approx(EXPECT[name])
    assert harness.reader("dedup_ms.backfill")({"spans": spans}) == \
        pytest.approx(2.0 / 160 * 1e3)
