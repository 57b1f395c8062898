"""Reduction from a profiler trace to device busy time, idle gaps and
kernel time.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples; everything else works on those tuples, so the tests can
check the arithmetic on a small recorded trace.  Device operations are the
events of each TPU plane's ``XLA Ops`` line; host events are those of the
host plane's threads (``TraceAnnotation`` spans among them).  The traced
window is bracketed by two host marks, ``OPEN`` and ``CLOSE``; device
operations are cut to the span between them, on the trace's own clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)
OPEN, CLOSE = "bench.window_open", "bench.window_close"


def read_xplane(log_dir: str) -> Dict[str, object]:
    """{"devices": {index: [Event]}, "host": [Event]} from the newest
    trace under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.duration_ns > 0 or e.name in (OPEN, CLOSE))
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """An op's name from its event name, which on the TPU is the whole HLO
    instruction (``%paged_attention_decode.5 = custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def busy_ns(ops: Sequence[Event]) -> float:
    """Nanoseconds in which at least one operation ran."""
    return covered((s, e) for _, s, e in ops)


def kernel_ns(ops: Sequence[Event], pattern: str) -> Tuple[float, int]:
    """Summed duration and count of the operations whose name matches."""
    rx = re.compile(pattern)
    hits = [e - s for n, s, e in ops if rx.search(n)]
    return float(sum(hits)), len(hits)


def op_family(name: str) -> str:
    """Group an HLO op name by dropping its numeric suffix."""
    return re.sub(r"[.\-_]?\d+$", "", name) or name


def top_ops(ops: Sequence[Event], n: int = 10) -> List[List]:
    acc: Dict[str, float] = {}
    for name, s, e in ops:
        k = op_family(name)
        acc[k] = acc.get(k, 0.0) + (e - s)
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def families(ops: Sequence[Event],
             n: int = 25) -> List[Tuple[str, int, float]]:
    """(op family, events, seconds), most time first."""
    acc: Dict[str, List[float]] = {}
    for name, s, e in ops:
        a = acc.setdefault(op_family(name), [0, 0.0])
        a[0] += 1
        a[1] += (e - s) * 1e-9
    return [(k, c, t) for k, (c, t) in
            sorted(acc.items(), key=lambda kv: -kv[1][1])[:n]]


def idle_gaps(ops: Sequence[Event], host: Sequence[Event],
              n: int = 10) -> List[List]:
    """The longest gaps between device operations, each named by the
    innermost host event that spans the gap's middle."""
    busy = union((s, e) for _, s, e in ops)
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] > a[1]]
    gaps.sort(reverse=True)
    out = []
    for g, s, e in gaps[:n]:
        mid = 0.5 * (s + e)
        around = [(he - hs, name) for name, hs, he in host
                  if hs <= mid <= he]
        label = min(around)[1] if around else "no host event"
        out.append([label, g * 1e-9])
    return out


def window(trace: Dict[str, object]) -> Dict[str, object]:
    """The trace cut to the span between the ``OPEN`` and ``CLOSE`` marks:
    device operations clipped to it, host events that overlap it, and the
    span itself as ``"window": (start_ns, end_ns)``."""
    marks = {n: (s, e) for n, s, e in trace["host"] if n in (OPEN, CLOSE)}
    if set(marks) != {OPEN, CLOSE}:
        raise ValueError(f"the trace lacks its window marks; found {marks}")
    lo, hi = marks[OPEN][1], marks[CLOSE][0]
    if not lo < hi:
        raise ValueError(f"window marks out of order: {marks}")
    clip = lambda ops: [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                        if e > lo and s < hi]
    return {"devices": {d: clip(ops) for d, ops in trace["devices"].items()},
            "host": [ev for ev in trace["host"]
                     if ev[2] > lo and ev[1] < hi
                     and ev[0] not in (OPEN, CLOSE)],
            "window": (lo, hi)}


def summarize(trace: Dict[str, object], chips: int) -> Dict:
    """busy_s (mean over the chips used), window_s and the breakdown of a
    trace cut by ``window``."""
    devices = trace["devices"]
    lo, hi = trace["window"]
    used = sorted(devices)[:chips]
    busy = [busy_ns(devices[d]) * 1e-9 for d in used]
    ops0 = devices[used[0]] if used else []
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": (hi - lo) * 1e-9,
            "breakdown": {"device_ops": top_ops(ops0),
                          "idle_gaps": idle_gaps(ops0, trace["host"])}}
