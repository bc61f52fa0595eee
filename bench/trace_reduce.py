"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to plain lists first (`load`), so that the arithmetic
below runs the same on a recorded trace in a CPU test as on a live one:

* ``device``: (op, start_ns, dur_ns) of every operation the first
  accelerator ran, from its plane's "XLA Ops" line. `op` is the HLO
  instruction's name without its number: a Pallas kernel's custom call is
  named after the jitted function that wraps it (`cadc_matmul_pallas`,
  `_conv_jit`, `_conv_q8_jit`), an XLA fusion after its kind. Ops that run
  inside a loop (a scanned layer stack) have events of their own, nested in
  the loop's event.
* ``host``: (name, start_ns, dur_ns) of every host span on the same
  clock, the benchmark's own `bench.*` annotations among them.

Busy time is the union of the device intervals, so nested and overlapping
operations count once; idle is the rest of a window. A kernel's time is
the sum of its events' durations.

The trace places device events against host spans only to within a
millisecond or so (the device clock is mapped onto the host's), so an
event at the edge of a host window can fall just outside it. A count that
has to be exact, such as a kernel's calls per forward, is taken over the
whole profiler session (`session`) where the driver drains the device
before the session starts and before it stops.
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, Union)

Event = Tuple[str, int, int]
Interval = Tuple[int, int]

DEVICE_PLANE = "/device:TPU:0"
DEVICE_LINE = "XLA Ops"
# ops whose event spans the events of the ops they run
CONTAINERS = frozenset({"while", "conditional", "call"})
_OP = re.compile(r"^%?([^\s=.]+)")


def op_name(event_name: str) -> str:
    """`%_conv_q8_jit.20 = f32[...] custom-call(...)` -> `_conv_q8_jit`,
    `%pad.12.clone = ...` -> `pad`: HLO names add only dotted suffixes."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def xplane_file(log_dir: str) -> str:
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"want one trace file under {log_dir}, found "
                           f"{files}")
    return files[0]


def load(path: str) -> Dict[str, List[Event]]:
    """Device ops of the first accelerator and all host spans of a trace
    file written by `jax.profiler`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: List[Event] = []
    host: List[Event] = []
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device.extend((op_name(e.name), int(e.start_ns),
                                   int(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    if not device:
        raise RuntimeError(f"no {DEVICE_LINE!r} events of {DEVICE_PLANE} in "
                           f"{path}")
    device.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals [a, b) into disjoint sorted ones."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], windows: Sequence[Interval]
         ) -> List[Interval]:
    """Parts of disjoint sorted `intervals` inside disjoint sorted
    `windows`."""
    out, j = [], 0
    for wa, wb in windows:
        while j < len(intervals) and intervals[j][1] <= wa:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < wb:
            a, b = max(intervals[k][0], wa), min(intervals[k][1], wb)
            if b > a:
                out.append((a, b))
            k += 1
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def overlap(s: int, e: int, windows: Sequence[Interval],
            starts: Sequence[int]) -> int:
    """Length of [s, e) inside disjoint sorted `windows` (whose starts are
    `starts`)."""
    n = 0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(windows) and windows[i][0] < e:
        n += max(0, min(e, windows[i][1]) - max(s, windows[i][0]))
        i += 1
    return n


def spans(host: Sequence[Event], name: str) -> List[Interval]:
    """Union of the host spans called `name`."""
    return union((s, s + d) for n, s, d in host if n == name)


def busy_ns(device: Sequence[Event], windows: Sequence[Interval]) -> int:
    """Time inside `windows` in which some operation ran on the device."""
    return total(clip(union((s, s + d) for _, s, d in device), windows))


def kernel_ns(device: Sequence[Event], windows: Optional[Sequence[Interval]],
              op: Union[str, Callable[[str], bool]]) -> Tuple[int, int]:
    """(summed duration, count) of the device events of op `op` (a name,
    or a test of the name) inside `windows`, or of all of them where
    `windows` is None."""
    match = op if callable(op) else (lambda n: n == op)
    starts = [w[0] for w in windows] if windows is not None else []
    ns = n = 0
    for name, s, d in device:
        if match(name) and (windows is None
                            or overlap(s, s + d, windows, starts)):
            ns += d
            n += 1
    return ns, n


def top_ops(device: Sequence[Event], windows: Sequence[Interval], k: int = 10
            ) -> List[List]:
    """The `k` ops that took most device time in `windows`, as [op,
    seconds]; loops are left out, since their bodies' ops are counted."""
    starts = [w[0] for w in windows]
    acc: Dict[str, int] = {}
    for name, s, d in device:
        if name in CONTAINERS:
            continue
        part = overlap(s, s + d, windows, starts)
        if part:
            acc[name] = acc.get(name, 0) + part
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in top]


def idle_gaps(device: Sequence[Event], host: Sequence[Event],
              windows: Sequence[Interval], k: int = 10) -> List[List]:
    """The `k` longest intervals inside `windows` with no device operation,
    each as [what the host was doing, seconds]: the name of the shortest
    host span that covers the gap's midpoint."""
    inside = clip(union((s, s + d) for _, s, d in device), windows)
    gaps: List[Interval] = []
    j = 0
    for wa, wb in windows:
        t = wa
        while j < len(inside) and inside[j][0] < wb:
            a, b = inside[j]
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
            j += 1
        if wb > t:
            gaps.append((t, wb))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) // 2
        cover = [(d, n) for n, s, d in host if s <= mid < s + d]
        out.append([min(cover)[1] if cover else "(no host span)",
                    (b - a) * 1e-9])
    return out


def window(events: Dict[str, List[Event]], windows: Sequence[Interval]
           ) -> Dict:
    """What every traced run records: the windows, their length and busy
    time, the breakdown, the device events inside them (`device`) and all
    device events of the profiler session (`session`), for the per-layer
    metrics' readers."""
    dev, host = events["device"], events["host"]
    starts = [w[0] for w in windows]
    inside = [e for e in dev if overlap(e[1], e[1] + e[2], windows, starts)]
    return {
        "windows": [list(w) for w in windows],
        "window_s": total(windows) * 1e-9,
        "busy_s": busy_ns(inside, windows) * 1e-9,
        "device": inside,
        "session": dev,
        "breakdown": {"device_ops": top_ops(inside, windows),
                      "idle_gaps": idle_gaps(inside, host, windows)},
    }
