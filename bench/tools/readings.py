"""Readings for a cell's check limit: the program's widest served gap on
many seeds, and with ``--control`` the float8 control's gap on the same
requests, judged in the program's place (``served_gap``, with the
program's own as ``program_gap``), all in one process (the benchmark's
own runs never run the control).

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds 20 [--control]

Prints one JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import main as hm, spec  # noqa: E402


def run():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    cell = spec.find_cell(a.workload)
    env = hm.open_device(cell)
    if env is None:
        return 3
    for seed in [int(x) for x in a.seeds.split(",")]:
        t = time.perf_counter()
        result, numbers = hm.execute(cell, seed, a.seconds, 0, env,
                                     t_start=t, control=a.control)
        print(json.dumps({"seed": seed, **numbers,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()},
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
