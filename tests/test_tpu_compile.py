"""Compile the main-path programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, primitives Mosaic cannot lower,
programs that do not fit). Interpret-mode tests cannot see any of that.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model import decode_step, init_cache
from repro.models.params import init_params


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def spec(one_chip, no_persistent_cache):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


# tinyllama-1.1b: 32 q-heads, 4 kv-heads, head dim 64, d_model 2048;
# mamba2-370m: 32 SSD heads x 64, d_state 128, chunk 256
KERNELS = {
    "flash_attention": lambda s: (
        lambda q, k, v: ops.flash_attention_op(q, k, v, interpret=False),
        (s((1, 512, 32, 64)), s((1, 512, 4, 64)), s((1, 512, 4, 64)))),
    "decode_attention": lambda s: (
        lambda q, k, v, n: ops.decode_attention_op(q, k, v, n,
                                                   interpret=False),
        (s((8, 32, 64)), s((8, 2048, 4, 64)), s((8, 2048, 4, 64)),
         s((8,), jnp.int32))),
    "paged_decode_attention": lambda s: (
        lambda q, kp, vp, t, n: ops.paged_decode_attention_op(
            q, kp, vp, t, n, interpret=False),
        (s((8, 32, 64)), s((513, 32, 4, 64)), s((513, 32, 4, 64)),
         s((8, 64), jnp.int32), s((8,), jnp.int32))),
    "ssd_scan": lambda s: (
        lambda x, dt, a, b, c: ops.ssd_scan_op(x, dt, a, b, c, chunk=256,
                                               interpret=False),
        (s((1, 512, 32, 64)), s((1, 512, 32)), s((32,)), s((1, 512, 128)),
         s((1, 512, 128)))),
    "rmsnorm": lambda s: (
        lambda x, w: ops.rmsnorm_op(x, w, interpret=False),
        (s((512, 2048)), s((2048,)))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, spec):
    fn, args = KERNELS[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_compiles_for_v5e(spec, one_chip):
    """Full-width tinyllama decode step, cut to 2 layers, over the
    serving batch: 8 slots x 2048 cache."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b"), n_layers=2)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, 8, 2048)))
    step = jax.jit(functools.partial(decode_step, cfg=cfg),
                   donate_argnames=("cache",))
    compiled = step.lower(params, cache=cache,
                          token=spec((8, 1), jnp.int32),
                          pos=spec((8,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
