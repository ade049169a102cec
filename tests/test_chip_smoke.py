"""``chip_smoke.py``'s phases at a reduced size on the CPU, its refusal to
run without a TPU, and the compile-cache helper the launchers share."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_engine_phases_paged_equals_contiguous(smoke):
    out = smoke.engine_phases(
        get_config("tinyllama-1.1b").reduced(), n_requests=6, min_chars=8,
        max_chars=40, max_new=4, n_slots=4, max_len=64, block_size=8,
        n_serial=3)
    contig, paged = out["contiguous"], out["paged"]
    assert len(contig["streams"]) == 6
    assert all(len(s) == 4 for s in contig["streams"])
    assert paged["streams"] == contig["streams"]
    assert contig["decode_steps"] > 0 and contig["peak_live"] == 4
    # batch-1 and batch-4 programs round alike on the CPU
    assert out["serial_divergence"] == [None, None, None]


def test_agent_phase_serves_every_run(smoke):
    out = smoke.agent_phase("tinyllama-1.1b", reduced=True, n_slots=4,
                            max_len=128, n_runs=2)
    assert len(out["success"]) == 2
    assert out["engine_steps"] > 0 and out["engine_tokens"] > 0
    assert out["llm_calls"] > 0


def test_first_divergence(smoke):
    assert smoke.first_divergence([1, 2, 3], [1, 2, 3]) is None
    assert smoke.first_divergence([1, 2, 3], [1, 5, 3]) == 1
    assert smoke.first_divergence([1, 2], [1, 2, 3]) == 2


def test_main_refuses_cpu(smoke, capsys, restore_cache_dir):
    assert jax.devices()[0].platform == "cpu"
    before = jax.config.jax_compilation_cache_dir
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out and "# serve" not in out
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
