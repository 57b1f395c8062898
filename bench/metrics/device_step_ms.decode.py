"""Model step, decode, on the device: for every two consecutive launches
that are both decode, the time device 0 is busy (union of its ops) from
the start of step N's ``launch.decode`` to the start of the next launch;
the median over those pairs, in ms.  The host spans and the device ops
share the profiler's clock.  It also logs where the rest of that period
goes: the device idle after its last op of step N until the host's
``sync.decode`` returns, and from the end of the next ``launch.decode``
to the device's first op of step N+1."""
import statistics
import sys

from bench import phases, trace_reduce


def read(rec):
    pairs = phases.decode_pairs(rec["trace"]["host"])
    ops = rec["trace"]["devices"].get(0, [])
    if not pairs or not ops:
        return None
    busy = trace_reduce.union((s, e) for _, s, e in ops)
    step = [phases.busy_in(busy, a[1], b[1]) for a, _, b in pairs]
    period = [b[1] - a[1] for a, _, b in pairs]
    to_sync = [sync[2] - phases.last_end(busy, sync[2])
               for _, sync, _ in pairs]
    starts = sorted(s for _, s, _ in ops)
    to_op = [phases.first_start(starts, b[1]) - b[2] for _, _, b in pairs]
    med = lambda xs: statistics.median(xs) * 1e-6
    print(f"[bench] decode step on the device: {len(pairs)} pairs; median "
          f"ms: period {med(period):.3f}, busy {med(step):.3f}, last op to "
          f"sync.decode end {med(to_sync):.3f}, launch.decode end to first "
          f"op {med(to_op):.3f}", file=sys.stderr)
    return med(step)
