"""Trace reduction: unions, own time, idle share, exposed collectives,
and gap attribution, on hand-made intervals and on a recorded trace.

``testdata/tpu_1chip.xplane.pb`` was recorded on one TPU v5 lite: three
steps of the qwen3-0.6b step at 1 x 4096 under ``jax.profiler.trace``
with the benchmark's host spans, cut down to those spans and to the
device operations within 13 ms around the boundary of two steps (the
loops that hold them included).
"""

from pathlib import Path

import pytest

from chipbench import reduce

DATA = Path(__file__).resolve().parent / "testdata"


def _trace(devices, spans):
    return reduce.Trace(devices=devices, spans=sorted(spans))


def test_merge_clips_and_joins():
    got = reduce.merge([(5, 8, "a"), (0, 2, "b"), (1, 3, "c"), (9, 20, "d")], 1, 12)
    assert got == [(1, 3), (5, 8), (9, 12)]
    assert reduce.length(got) == 2 + 3 + 3


def test_subtract():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert reduce.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert reduce.subtract(a, []) == a


def test_own_time_excludes_children():
    ops = [(0, 100, "while.1"), (10, 30, "fusion.2"), (40, 50, "fusion.3"),
           (60, 100, "while.4"), (70, 80, "copy.5")]
    leaves, own = reduce.leaves_and_self(ops)
    assert own["while.1"] == 100 - 20 - 10 - 40
    assert own["while.4"] == 40 - 10
    assert {name for _, _, name in leaves} == {"fusion.2", "fusion.3", "copy.5"}


def test_idle_share_and_exposed_collective():
    # window [0, 100): device busy [0, 60) and [70, 90); a permute runs
    # [50, 75), overlapped by compute until 60 and from 70: exposed 10
    dev = [(0, 60, "fusion.1"), (50, 75, "collective-permute-done.3"),
           (70, 90, "fusion.2")]
    spans = [(0, 40, "bench.dispatch"), (40, 100, "bench.wait")]
    tr = _trace({"/device:TPU:0": dev}, spans)
    assert tr.window_s == pytest.approx(100e-9)
    assert reduce.busy_s(tr) == pytest.approx(90e-9)
    assert reduce.idle_share(tr) == pytest.approx(0.10)
    assert reduce.exposed_share(tr, "collective-permute") == pytest.approx(0.10)
    assert reduce.idle_gaps(tr) == [["bench.wait", pytest.approx(10e-9)]]


def test_shares_average_over_devices():
    spans = [(0, 100, "bench.wait")]
    tr = _trace({"/device:TPU:0": [(0, 100, "f")], "/device:TPU:1": [(0, 50, "f")]}, spans)
    assert reduce.idle_share(tr) == pytest.approx(0.25)
    assert reduce.top_ops(tr) == [["f", pytest.approx(75e-9)]]


def test_op_name():
    assert reduce.op_name("%fusion.699 = (f32[8]{0}) fusion(f32[8]{0} %p)") == "fusion.699"
    assert reduce.op_name("copy-start.3") == "copy-start.3"


def _sweep_union(ops, lo, hi):
    """Busy time by a sweep over start (+1) and end (-1) events."""
    edges = sorted([(max(s, lo), 1) for s, e, _ in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e, _ in ops if e > lo and s < hi])
    depth, last, busy = 0, lo, 0.0
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.xplane.pb")))
def test_recorded_trace(name):
    tr = reduce.load(DATA / name)
    assert tr.devices and all(reduce.DEVICE_PLANE.match(d) for d in tr.devices)
    assert {n for _, _, n in tr.spans} == {"bench.input", "bench.dispatch", "bench.wait"}
    lo, hi = tr.window
    for ops in tr.devices.values():
        assert ops
        exact = reduce.length(reduce.merge(ops, lo, hi))
        assert exact == pytest.approx(_sweep_union(ops, lo, hi), rel=1e-9)
    assert 0 < reduce.busy_s(tr) <= tr.window_s
    assert 0 <= reduce.idle_share(tr) < 1
    top = reduce.top_ops(tr)
    assert 0 < sum(s for _, s in top) <= reduce.busy_s(tr) * 1.0001
    for span, seconds in reduce.idle_gaps(tr):
        assert span.startswith("bench.") or span == "none"
        assert 0 < seconds <= tr.window_s
