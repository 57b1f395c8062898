"""Engine loop: the share of the decode steps started in the traced
window whose launch built its plan anew instead of using one staged
while the previous step ran (the tracer's decode step spans and their
``staged`` arg), in %."""
from bench.records import ENGINE_PID


def read(rec):
    tr = rec["tracer"]
    staged = []
    for ev in tr.events:
        if ev.get("pid") == ENGINE_PID and ev.get("tid") == 0 \
                and ev.get("ph") == "X" and ev["name"] == "decode" \
                and "staged" in ev["args"] \
                and rec["t0"] <= tr.t0 + ev["ts"] * 1e-6 < rec["t1"]:
            staged.append(ev["args"]["staged"])
    if not staged:
        return None
    return 100.0 * staged.count(False) / len(staged)
