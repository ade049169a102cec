"""Print a traced window's device idle time by the innermost program
span open at each instant, and the mean in-step idle time (read-only,
like ``trace_peek.py``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds 51 \\
        --trace 1 --keep-trace <dir>
    python3 bench/tools/idle_by_span.py <dir>/<file>.xplane.pb

``(no program span)`` is idle time outside every ``BatchScheduler.*``
span: the harness waiting for arrivals or between steps.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spans, trace  # noqa: E402


def main(path: str) -> int:
    dev, host = trace.read_xspace(path)
    p = spans.summarize(dev, host)
    if p is None:
        print("no traced window, device or BatchScheduler.step span")
        return 1
    window_ms = (p.window[1] - p.window[0]) / 1e6
    idle_ms = sum(p.idle_ns.values()) / 1e6 or float("nan")
    print(f"window {window_ms:.1f} ms, device idle {idle_ms:.1f} ms "
          f"({100 * idle_ms / window_ms:.2f} %), {len(p.steps)} steps")
    print(f"{'innermost span':<36}{'idle ms':>12}{'share %':>10}")
    for name, ns in sorted(p.idle_ns.items(), key=lambda kv: -kv[1]):
        print(f"{name:<36}{ns / 1e6:>12.1f}{100 * ns / 1e6 / idle_ms:>10.2f}")
    idle = p.step_idle_ns
    print(f"step_idle_ms {sum(idle) / len(idle) / 1e6:.4f} "
          f"(mean over {len(idle)} BatchScheduler.step spans)")
    print(f"idle inside steps under a child span: "
          f"{100 * spans.under_a_child(p):.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
