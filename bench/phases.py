"""What the engine-loop readers read: the program's own phases
(``Tracer.phase`` with ``jax_annotations``), written by
``jax.profiler.TraceAnnotation`` into the profiler's host plane on the
device trace's clock and cut to the traced window by
``trace_reduce.window``.  A phase is a host event named ``admit``,
``dispatch``, ``schedule``, ``plan``, ``upload``, ``launch.<kind>``,
``stage``, ``collect``, ``sync.<kind>``, ``emit`` or ``results``."""
from __future__ import annotations

import bisect
from typing import Dict, List, Sequence, Tuple

from bench import trace_reduce

Event = trace_reduce.Event
NAMES = {"admit", "dispatch", "schedule", "plan", "upload", "stage",
         "collect", "emit", "results"}
PREFIXES = ("launch.", "sync.")


def is_phase(name: str) -> bool:
    return name in NAMES or name.startswith(PREFIXES)


def decode_pairs(host: Sequence[Event]) -> List[Tuple[Event, Event, Event]]:
    """(launch N, sync N, launch N+1) for every two consecutive launches
    that are both ``launch.decode``; sync N is the first ``sync.decode``
    that starts between them."""
    launches = sorted((e for e in host if e[0].startswith("launch.")),
                      key=lambda e: e[1])
    syncs = sorted((e for e in host if e[0] == "sync.decode"),
                   key=lambda e: e[1])
    starts = [s for _, s, _ in syncs]
    out = []
    for a, b in zip(launches, launches[1:]):
        if a[0] != "launch.decode" or b[0] != "launch.decode":
            continue
        i = bisect.bisect_left(starts, a[2])
        if i < len(syncs) and syncs[i][1] < b[1]:
            out.append((a, syncs[i], b))
    return out


def busy_in(busy: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Nanoseconds of the sorted disjoint intervals ``busy`` inside
    [lo, hi)."""
    i = max(bisect.bisect_right(busy, (lo, float("inf"))) - 1, 0)
    total = 0.0
    for s, e in busy[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def last_end(busy: Sequence[Tuple[float, float]], t: float) -> float:
    """End of the device's last busy interval that starts before ``t``,
    capped at ``t`` (-inf when there is none)."""
    i = bisect.bisect_left(busy, (t,)) - 1
    return min(busy[i][1], t) if i >= 0 else float("-inf")


def first_start(starts: Sequence[float], t: float) -> float:
    """The first of the sorted op ``starts`` at or after ``t`` (inf when
    there is none).  Op starts, not busy intervals: an op still running
    at ``t`` would merge the next step's ops into its interval."""
    i = bisect.bisect_left(starts, t)
    return starts[i] if i < len(starts) else float("inf")


def innermost(host: Sequence[Event], lo: float,
              hi: float) -> Dict[str, float]:
    """Nanoseconds of [lo, hi) by the innermost phase open at each
    instant (the phase that started last among those covering it), with
    ``"none"`` where no phase is open."""
    evs = [(n, max(s, lo), min(e, hi)) for n, s, e in host
           if is_phase(n) and e > lo and s < hi]
    cuts = sorted({lo, hi, *(s for _, s, _ in evs), *(e for *_, e in evs)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s, n) for n, s, e in evs if s <= a and e >= b]
        name = max(open_)[1] if open_ else "none"
        out[name] = out.get(name, 0.0) + (b - a)
    return out
