"""What the per-layer readers read from a traced serving run: the engine
tracer's step and request spans, and the engine-side token events, all on
the host's ``perf_counter`` clock and cut to the traced window."""
from __future__ import annotations

from typing import Dict, List, Tuple

ENGINE_PID, REQUEST_PID = 1, 2


def _abs(tracer, ev) -> Tuple[float, float]:
    t0 = tracer.t0 + ev["ts"] * 1e-6
    return t0, t0 + ev.get("dur", 0.0) * 1e-6


def steps(rec, kinds) -> List[Tuple[str, float, float]]:
    """Engine steps of these kinds that started in the traced window."""
    tr = rec["tracer"]
    out = []
    for ev in tr.events:
        if ev.get("pid") == ENGINE_PID and ev.get("tid") == 0 \
                and ev.get("ph") == "X" and ev["name"] in kinds:
            s, e = _abs(tr, ev)
            if rec["t0"] <= s < rec["t1"]:
                out.append((ev["name"], s, e))
    return sorted(out, key=lambda x: x[1])


def chunk_rows(rec) -> Dict[float, List[Tuple[int, int, int]]]:
    """Prefill rows by the start time of their engine step:
    {step start: [(rid, n_done, n_chunk), ...]}."""
    tr = rec["tracer"]
    out: Dict[float, list] = {}
    for ev in tr.events:
        if ev.get("pid") == REQUEST_PID and ev["name"] == "prefill_chunk":
            s, _ = _abs(tr, ev)
            a = ev["args"]
            out.setdefault(s, []).append((ev["tid"], a["n_done"],
                                          a["n_chunk"]))
    return out


def prefill_rows(rec):
    """(rid, n_done, n_chunk, is_last_chunk) of every prefill row whose step
    started in the traced window, with the step's (start, end)."""
    rows = chunk_rows(rec)
    out = []
    for _, s, e in steps(rec, ("prefill", "prefill_chunk")):
        for rid, n_done, n in rows.get(s, []):
            last = n_done + n >= rec["prompt_len"].get(rid, 0)
            out.append((rid, n_done, n, last, s, e))
    return out


def decoded(rec, t0=None, t1=None) -> List[Tuple[float, int]]:
    """(engine time, context) of every token a decode step produced in
    ``[t0, t1)`` (the traced window by default).  Token ``i >= 1`` of a
    request with a ``P``-token prompt was decoded over ``P + i`` keys."""
    t0 = rec["t0"] if t0 is None else t0
    t1 = rec["t1"] if t1 is None else t1
    P = rec["prompt_len"]
    return [(t, P[rid] + i) for t, rid, i in rec["tokens"]
            if i >= 1 and t0 <= t < t1 and rid in P]

