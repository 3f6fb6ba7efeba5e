"""The trace reduction on a small trace recorded on the CPU backend.

``data/small_trace.xplane.pb``: three executions of a jitted ``step``
(a matmul, a tanh, a matmul), each inside ``bench.push`` with the call
under ``bench.dispatch``, followed by a 20 ms sleep under ``bench.poll``.
The operation intervals below are read off the file by hand; every
expected number is worked out from them here.
"""
import pathlib

import numpy as np
import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# (name, start_ns, end_ns) of the nine operations, in stream order
OPS = [
    ("dot_general.2", 266466, 2022607),
    ("wrapped_tanh", 2023938, 2489857),
    ("dot_general.3", 2491305, 3655757),
    ("dot_general.2", 24591565, 26621441),
    ("wrapped_tanh", 26622947, 27025366),
    ("dot_general.3", 27025908, 28818947),
    ("dot_general.2", 49476950, 51406907),
    ("wrapped_tanh", 51408886, 52268246),
    ("dot_general.3", 52270421, 54072350),
]
PUSH_WINDOW = (32484, 74443656)      # first bench.push start, last end


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(DATA / "small_trace.xplane.pb"))


def test_events_read(tr):
    assert list(tr.ops) == ["CPU:0"]
    got = [(n, int(a), int(b)) for n, _, a, b in tr.ops["CPU:0"]]
    assert got == OPS
    assert [e[0] for e in tr.executions["CPU:0"]] == ["jit_step"] * 3
    names = [a[0] for a in tr.annotations]
    assert names.count("bench.push") == 3
    assert names.count("bench.poll") == 3
    assert names.count("bench.dispatch") == 3


def test_busy_idle_and_step_time(tr):
    red = trace.reduce_trace(tr, PUSH_WINDOW)
    busy_ns = sum(b - a for _, a, b in OPS)          # no two ops overlap
    assert busy_ns == 12203092
    win_ns = PUSH_WINDOW[1] - PUSH_WINDOW[0]
    assert red["busy_s"]["CPU:0"] == pytest.approx(busy_ns * 1e-9)
    assert red["window_s"] == pytest.approx(win_ns * 1e-9)
    assert red["idle_share"] == pytest.approx(1 - busy_ns / win_ns)
    assert red["step_module"] == "jit_step"
    assert red["step_executions"] == 3
    assert red["step_device_s"] == pytest.approx(busy_ns / 3 * 1e-9)


def test_top_ops_and_gaps(tr):
    red = trace.reduce_trace(tr, PUSH_WINDOW)
    per_op = {}
    for n, a, b in OPS:
        per_op[n] = per_op.get(n, 0) + b - a
    top = sorted(per_op.items(), key=lambda kv: -kv[1])
    assert [n for n, _ in red["top_ops"]] == [n for n, _ in top]
    assert red["top_ops"][0][1] == pytest.approx(top[0][1] * 1e-9)
    # the two longest gaps lie between executions, in the 20 ms sleeps
    g1 = (OPS[3][1] - OPS[2][2]) * 1e-9
    g2 = (OPS[6][1] - OPS[5][2]) * 1e-9
    gaps = red["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["bench.poll"] * 3
    assert sorted(g[1] for g in gaps[:2]) == pytest.approx(sorted(
        [g1, g2]))
    # every idle nanosecond of the window is in some labelled gap
    idle = sum(s for _, s in red["idle_by_label"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s_mean"])


def test_merge_and_clip():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m.tolist() == [[0, 3], [5, 9]]
    assert trace.clip(m, 2, 6).tolist() == [[2, 3], [5, 6]]
    assert trace.merge([]).shape == (0, 2)
    assert np.all(trace.clip(m, 10, 11) == np.zeros((0, 2)))
