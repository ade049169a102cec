"""Find an agent cell's knee once: the highest session rate at which
completions keep pace with arrivals, with no queue growing through the
window.  One process, one engine; each rate gets a window of its own on
a fresh scheduler.

    python3 bench/tools/sweep.py --workload <cell> --rates 0.2,0.3,0.4 \\
        --seconds 30 --seed 5

Prints one JSON line per rate: offered requests per second, how many
were still unfinished when the window closed and how long the drain
took, and the TTFT tail of the requests due in the first and in the
second half of the window (a growing queue shows as a later half far
slower than the earlier one).
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import main as hm, serve, spec, traffic  # noqa: E402
from harness.stats import percentile  # noqa: E402


def run():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    a = ap.parse_args()
    cell = spec.find_cell(a.workload)
    env = hm.open_device(cell)
    if env is None:
        return 3
    from repro.serving.scheduler import BatchScheduler
    cfg = spec.model_config(cell.config)
    sv, m = cell.serving, cell.model
    vocab, max_len = m["vocab_size"], int(sv["max_len"])
    engine, sched = serve.build(cell, cfg, a.seed)
    for rate in [float(x) for x in a.rates.split(",")]:
        mix = copy.deepcopy(cell.mix)
        mix["session_rate_per_s"] = rate
        sched = BatchScheduler(engine, n_slots=int(sv["n_slots"]),
                               max_len=max_len, paged_kv=True,
                               block_size=int(sv["block_size"]),
                               prefix_cache=True)
        shapes = traffic.warm_shapes(mix, a.seed, a.seconds, vocab, max_len,
                                     int(sv["prefill_chunk"]))
        serve.warm_up(sched, shapes, vocab, a.seed)
        reqs = traffic.agent_requests(mix, a.seed, a.seconds, vocab, max_len)
        t = time.perf_counter()
        w = serve.run_window(sched, mix, reqs, a.seconds)
        half = a.seconds / 2

        def ttft(rs):
            return percentile([(r.first if r.first is not None
                                else w.drain_deadline) - r.due for r in rs], 90)

        early = [r for r in w.requests if r.due < half]
        late = [r for r in w.requests if r.due >= half]
        print(json.dumps({
            "rate": rate, "requests": len(w.requests),
            "offered_req_s": len(w.requests) / a.seconds,
            "unfinished_at_close": sum(1 for r in w.requests
                                       if r.done is None or r.done > a.seconds),
            "failed": sum(1 for r in w.requests if r.done is None),
            "drain_s": max((r.done or w.drain_deadline) for r in w.requests)
            - a.seconds if w.requests else 0.0,
            "ttft_p90_early_s": ttft(early), "ttft_p90_late_s": ttft(late),
            "steps": len(w.steps),
            "mean_live": sum(s.counters["live"] for s in w.steps)
            / max(1, len(w.steps)),
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
