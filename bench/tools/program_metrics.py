"""The program's own readings of one run of a cell: queue wait, prefill
padding and in-step device idle, from ``Request.t_admit``,
``EngineStepped.prefill_rows`` and the ``BatchScheduler.*`` spans.

    python3 bench/tools/program_metrics.py --workload <cell> --seed <n> \\
        --seconds <s> [--trace 1]

Runs the cell's set-up, warm-up and window as ``bench/run.py`` does (no
check), carries those stamps, counters and spans onto the harness's
records, and prints one JSON line with what the readers
``bench/metrics/{queue_wait_p90_ms,prefill_pad_share,step_idle_ms}.py``
make of them (``step_idle_ms`` only with ``--trace 1``).  The
benchmark's own runs do not carry these fields.
"""
import argparse
import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import main as hm, serve, spans, spec, trace, traffic  # noqa: E402
from harness.counts import Counts  # noqa: E402


def carry(window, sched, events, t0: float) -> None:
    """Put the program's admission stamps on the harness's requests
    (``admit``, seconds after the window opened; None if never admitted)
    and each step's prefill rows on its ``Step``."""
    assert len(events) == len(window.steps)
    for s, ev in zip(window.steps, events):
        s.prefill_rows = ev.prefill_rows
    for r in window.requests:
        q = sched.requests.get(r.rid)
        r.admit = q.t_admit - t0 if q is not None and q.t_admit else None


def measure(cell, seed: int, seconds: float, traced: bool, env: dict):
    """One run's window with the program's fields carried; returns the
    readings, the window and the scheduler."""
    m, sv, mix = cell.model, cell.serving, cell.mix
    max_len, chunk = int(sv["max_len"]), int(sv["prefill_chunk"])
    _, sched = serve.build(cell, spec.model_config(cell.config), seed)
    shapes = traffic.warm_shapes(mix, seed, seconds, m["vocab_size"],
                                 max_len, chunk)
    serve.warm_up(sched, shapes, m["vocab_size"], seed)
    if mix["kind"] == "agent":
        reqs = traffic.agent_requests(mix, seed, seconds, m["vocab_size"],
                                      max_len)
    else:
        reqs = traffic.batch_requests(mix, seed, m["vocab_size"], max_len)
    events, opened = [], []
    sched.subscribe(events.append)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    window = serve.run_window(sched, mix, reqs, seconds, trace_dir=trace_dir,
                              on_open=lambda: opened.append(
                                  time.perf_counter()))
    carry(window, sched, events, opened[0])
    summary = program = None
    if trace_dir is not None:
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        if files:
            dev, host = trace.read_xspace(files[0])
            summary = trace.summarize(dev, host)
            program = spans.summarize(dev, host)
        shutil.rmtree(trace_dir, ignore_errors=True)
    data = hm.RunData(cell, window, Counts.of(m, sv["dtype"]), env["peaks"],
                      summary, chunk)
    data.spans = program
    kind = mix["kind"]
    names = [f"prefill_pad_share.{kind}", f"step_idle_ms.{kind}"]
    if kind == "agent":
        names.insert(0, "queue_wait_p90_ms")
    out = {"workload": cell.name, "seed": seed, "steps": len(window.steps)}
    out.update({n: spec.metric_reader(n)(data) for n in names})
    if program is not None:
        out["idle_ms_by_span"] = {k: v / 1e6 for k, v in
                                  sorted(program.idle_ns.items(),
                                         key=lambda kv: -kv[1])}
        out["idle_under_a_child"] = spans.under_a_child(program)
    return out, window, sched


def run():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    cell = spec.find_cell(a.workload)
    env = hm.open_device(cell)
    if env is None:
        return 3
    out, _, _ = measure(cell, a.seed, a.seconds, bool(a.trace), env)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
