"""The check that decides ``correct`` fails what it must fail, at a size
a test run holds: the float8 control, put in the program's place, reads
above the limit that sound runs keep under; and whole harness runs with
the timed path broken underneath come out not correct, for each fault a
serving cell can have: a token altered where it is sampled, and a decode
step that leaves the cache state unchanged."""
import time

import jax.numpy as jnp
import pytest

import bench_tiny
from harness import main as hm
from harness import spec

SEEDS = (11, 2 ** 31 + 12, 13)


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("agent"), "agent")
    cell = spec.find_cell("tiny.agent", root=root)
    return root, cell, hm.open_device(cell, require_accelerator=False)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_passes_and_the_control_fails(agent, seed):
    root, cell, env = agent
    result, nums = hm.execute(cell, seed, 2.0, 0, env,
                              t_start=time.perf_counter(), root=root,
                              control=True)
    # the program's own tokens keep under the limit; the control's picks,
    # judged in their place by the same comparison, do not
    assert result["attempted"] > 0 and result["failed"] == 0
    assert nums["program_gap"] <= bench_tiny.TINY_LIMIT < nums["served_gap"]
    assert not result["correct"]
    assert result["check"]["served_gap"]["value"] == nums["served_gap"]


def test_control_flag_of_a_whole_run_reads_not_correct(tmp_path, capsys):
    root = bench_tiny.make_root(tmp_path, "agent")
    out = bench_tiny.run(root, "agent", capsys, extra=["--control", "1"])
    assert out["correct"] is False
    assert out["check"]["served_gap"]["value"] > bench_tiny.TINY_LIMIT


def _alter_tokens(engine, sched):
    sample = engine.sample
    vocab = engine.cfg.vocab_size

    def altered(logits, rids, steps):
        return (sample(logits, rids, steps) + 1) % (vocab - 3)

    engine.sample = altered


def _decode_keeps_state(engine, sched):
    sched._scatter_rows = lambda pool, view, tables, pos: pool


@pytest.mark.parametrize("fault", [_alter_tokens, _decode_keeps_state],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(agent, fault):
    root, cell, env = agent
    result, nums = hm.execute(cell, SEEDS[0], 2.0, 0, env,
                              t_start=time.perf_counter(), root=root,
                              fault=fault)
    assert not result["correct"]
    assert nums["served_gap"] > bench_tiny.TINY_LIMIT


def test_result_line_of_a_whole_run(tmp_path, capsys):
    root = bench_tiny.make_root(tmp_path, "batch")
    out = bench_tiny.run(root, "batch", capsys)
    assert list(out)[-1] == "check" and out["correct"] is True
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["metrics"]["output_tok_s"]["unit"] == "tokens/s"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert out["check"]["served_gap"]["limit"] == bench_tiny.TINY_LIMIT


def test_no_accelerator_exits_without_a_result(tmp_path, capsys):
    from harness.main import main
    root = bench_tiny.make_root(tmp_path, "batch")
    rc = main(["--workload", "tiny.batch", "--seed", "1", "--seconds", "1",
               "--trace", "0"], time.perf_counter(), root=root)
    assert rc != 0 and capsys.readouterr().out == ""
