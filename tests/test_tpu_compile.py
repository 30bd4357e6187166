"""Compile rehearsals for a v5e chip: the served programs at real widths.

Each test compiles a program of the served path for one chip of a
described (not attached) ``v5e:2x2`` topology and checks that what the
compiler reports fits the chip's 16 GB of HBM.  Nothing runs, so these
say nothing about results or speed -- they catch what the TPU compiler
refuses (lowering errors, memory that does not fit) at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.launch import serve
from repro.models import transformer as tf
from repro.serving.device_cache import DeviceCacheConfig, STDDeviceCache

HBM_BYTES = 16 * 10**9  # one v5e chip
ENTRIES = 1 << 16  # the table at which one served-step compile takes ~2 s
VALUE_DIM = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    return used


@pytest.mark.parametrize("batch", [256, 4096])
def test_serve_one_call_compiles_for_v5e(one_chip, batch):
    """The jnp one-call serve step (the chip's served program) at the
    serve CLI's STDv_SDC_C2 layout: W=8, V=8, half the table static."""
    cfg = DeviceCacheConfig.build(
        ENTRIES, 0.5, 0.4, {t: 1000 + t for t in range(16)},
        ways=8, value_dim=VALUE_DIM,
    )
    cache = STDDeviceCache(cfg)
    n_static = int(0.7 * ENTRIES)
    s, w = cache.n_sets, cfg.ways

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = {
        "ks": sds((s, 4 * w), jnp.uint32),
        "value": sds((s, w, VALUE_DIM), jnp.int32),
        "clock": sds((), jnp.int32),
        "static_hi": sds((n_static,), jnp.uint32),
        "static_lo": sds((n_static,), jnp.uint32),
        "static_value": sds((n_static, VALUE_DIM), jnp.int32),
    }
    plan = (
        sds((batch,), jnp.int32), sds((batch,), bool),
        sds((batch,), jnp.int32), sds((batch, VALUE_DIM), jnp.int32),
    )
    request = (
        sds((batch,), jnp.uint32), sds((batch,), jnp.uint32),
        sds((batch,), jnp.int32), sds((batch,), bool),
        sds((batch,), jnp.uint32), sds((batch,), jnp.uint32),
    )
    step = jax.jit(functools.partial(cache.serve_one_call, use_kernel=False))
    compiled = step.lower(state, *plan, *request).compile()
    assert _fits(compiled) > 0


def test_miss_backend_compiles_for_v5e(one_chip):
    """The serve CLI's miss backend at its chunk shape (4096 queries x 8
    tokens): the static-layer preload calls it in such chunks, since the
    whole static set in one call does not fit the chip."""
    mcfg = get_arch("gemma-2b").smoke_config
    params = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), mcfg))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), params
    )
    tokens = jax.ShapeDtypeStruct((4096, 8), jnp.int32, sharding=one_chip)
    scores = jax.jit(
        functools.partial(serve.model_scores, mcfg=mcfg, value_dim=VALUE_DIM)
    )
    compiled = scores.lower(params, tokens).compile()
    assert _fits(compiled) > 0
