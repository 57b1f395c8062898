"""Engine loop between two decode steps: the host time from the end of
step N's ``sync.decode`` (its tokens read back) to the end of step N+1's
``launch.decode`` (the next step handed to the device), for every two
consecutive launches that are both decode, on the profiler's clock; the
median over those pairs, in ms.  It also logs how the median gap splits
by the innermost program phase open at each instant, and the share of
the gaps that some phase covers."""
import statistics
import sys

from bench import phases


def read(rec):
    host = rec["trace"]["host"]
    pairs = phases.decode_pairs(host)
    if not pairs:
        return None
    gaps = [(sync[2], b[2]) for _, sync, b in pairs]
    split = {}
    for lo, hi in gaps:
        for name, ns in phases.innermost(host, lo, hi).items():
            split.setdefault(name, []).append(ns)
    total = sum(hi - lo for lo, hi in gaps)
    covered = 1.0 - sum(split.get("none", [])) / total
    med = {n: statistics.median(v + [0.0] * (len(gaps) - len(v))) * 1e-6
           for n, v in split.items()}
    print(f"[bench] host gap between decode steps: {len(gaps)} pairs, "
          f"phases cover {100 * covered:.2f}% of the gaps; median ms by "
          "innermost phase: " + ", ".join(
              f"{n} {v:.3f}" for n, v in
              sorted(med.items(), key=lambda kv: -kv[1])),
          file=sys.stderr)
    return statistics.median(hi - lo for lo, hi in gaps) * 1e-6
