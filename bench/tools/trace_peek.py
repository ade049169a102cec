"""Print what a profiler trace holds: planes, lines, event counts and a
few events of each, then the benchmark's reduction of it.

    python3 bench/tools/trace_peek.py <file.xplane.pb>
"""
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from jax.profiler import ProfileData  # noqa: E402

from harness import trace  # noqa: E402

data = ProfileData.from_file(sys.argv[1])
for plane in data.planes:
    lines = list(plane.lines)
    print(f"PLANE {plane.name!r} lines={len(lines)}")
    for ln in lines:
        evs = list(ln.events)
        names = Counter(e.name for e in evs)
        print(f"  LINE {ln.name!r} events={len(evs)} top={names.most_common(6)}")
        for e in evs[:2]:
            print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}")
dev, host = trace.read_xspace(sys.argv[1])
s = trace.summarize(dev, host)
if s is not None:
    print("window_s", s.window_s, "busy_s", s.busy_s, "steps", len(s.steps))
    print("top_ops", s.top_ops)
    print("idle_gaps", s.idle_gaps)
