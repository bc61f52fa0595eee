"""The reduction from a profiler trace to per-layer numbers: union of
device intervals, idle share, per-kernel sums, top operations and idle
gaps, on small hand-made event lists."""
import pytest

import trace_reduce as tr

# (name, start_ns, dur_ns)
DEVICE = [
    ("fusion.1", 100, 50),       # 100-150
    ("_kernel", 140, 60),        # 140-200, overlaps fusion.1
    ("_kernel", 300, 100),       # 300-400
    ("copy.2", 390, 20),         # 390-410
    ("_q8_kernel", 900, 50),     # 900-950, outside the windows
]
HOST = [
    ("bench.step", 90, 330),     # 90-420
    ("bench.wait", 420, 200),
    ("bench.step", 620, 100),    # 620-720: no device op in it
]


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_clip_to_windows():
    assert tr.clip([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]


def test_busy_counts_overlap_once():
    win = tr.spans(HOST, "bench.step")
    assert win == [(90, 420), (620, 720)]
    # 100-200 and 300-410 inside the windows
    assert tr.busy_ns(DEVICE, win) == 100 + 110
    idle = 1 - tr.busy_ns(DEVICE, win) / tr.total(win)
    assert idle == pytest.approx(1 - 210 / 430)


def test_kernel_sums_only_inside_windows():
    win = tr.spans(HOST, "bench.step")
    assert tr.kernel_ns(DEVICE, win, lambda n: n == "_kernel") == (160, 2)
    assert tr.kernel_ns(DEVICE, win, lambda n: n == "_q8_kernel") == (0, 0)


def test_kernel_sums_over_the_whole_session():
    assert tr.kernel_ns(DEVICE, None, "_kernel") == (160, 2)
    assert tr.kernel_ns(DEVICE, None, "_q8_kernel") == (50, 1)


def test_top_ops_by_time():
    win = tr.spans(HOST, "bench.step")
    top = tr.top_ops(DEVICE, win, k=2)
    assert [n for n, _ in top] == ["_kernel", "fusion.1"]
    assert top[0][1] == pytest.approx(160e-9)


def test_idle_gaps_name_the_host_span():
    win = tr.spans(HOST, "bench.step")
    gaps = tr.idle_gaps(DEVICE, HOST, win, k=3)
    # longest: 620-720 (100 ns), then 200-300 (100 ns), then 90-100
    assert sorted(round(s * 1e9) for _, s in gaps) == [10, 100, 100]
    assert all(name == "bench.step" for name, _ in gaps)
