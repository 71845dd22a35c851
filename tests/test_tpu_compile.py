"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

Each case lowers one kernel at DeepSeek-R1 widths (d_model 7168, expert
d_ff 2048, dense d_ff 18432 split four ways, bf16) and compiles it with
the TPU compiler for a described ``v5e:2x2`` topology — no chip needed.
That catches what interpret mode cannot: tiles that break Mosaic's
(8, 128) block rule and steps that overrun the scoped VMEM limit.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
test workers all import this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.split_gemm import (
    split_dense_swiglu,
    split_grouped_swiglu,
    split_grouped_swiglu_demand,
    split_reduce_gemm,
    split_stack_gemm,
)

D = 7168           # DeepSeek-R1 d_model
F_EXPERT = 2048    # routed / shared expert d_ff
F_SLICE = 18432 // 4  # dense d_ff split over G'=4 ranks
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(
        lambda *a: fn(*a, interpret=False, **static)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [1, 8, 32, 256, 1282])
@pytest.mark.parametrize("e_l,e_r", [(16, 0), (4, 12)])
def test_split_grouped_swiglu_compiles(one_chip, no_persistent_cache,
                                       e_l, e_r, c):
    e = e_l + e_r
    _compile(
        split_grouped_swiglu, one_chip,
        ((e, c, D), BF16),
        ((e_l, D, F_EXPERT), BF16), ((e_l, D, F_EXPERT), BF16),
        ((e_l, F_EXPERT, D), BF16),
        ((e_r, D, F_EXPERT), BF16), ((e_r, D, F_EXPERT), BF16),
        ((e_r, F_EXPERT, D), BF16),
    )


@pytest.mark.parametrize("c", [1, 8])
def test_split_grouped_swiglu_demand_compiles(one_chip, no_persistent_cache,
                                              c):
    e_l, e_f = 4, 8
    _compile(
        split_grouped_swiglu_demand, one_chip,
        ((e_l + e_f, c, D), BF16),
        ((e_l, D, F_EXPERT), BF16), ((e_l, D, F_EXPERT), BF16),
        ((e_l, F_EXPERT, D), BF16),
        ((e_f, D, F_EXPERT), BF16), ((e_f, D, F_EXPERT), BF16),
        ((e_f, F_EXPERT, D), BF16),
        ((e_f,), jnp.bool_),
    )


# 8217: an odd prompt length, zero-padded to whole token blocks
@pytest.mark.parametrize("t", [8, 2048, 8217])
def test_split_dense_swiglu_compiles(one_chip, no_persistent_cache, t):
    _compile(
        split_dense_swiglu, one_chip,
        ((t, D), BF16),
        ((1, D, F_SLICE), BF16), ((1, D, F_SLICE), BF16),
        ((1, F_SLICE, D), BF16),
        ((3, D, F_SLICE), BF16), ((3, D, F_SLICE), BF16),
        ((3, F_SLICE, D), BF16),
    )


@pytest.mark.parametrize("t", [8, 2048])
def test_split_stack_gemm_compiles(one_chip, no_persistent_cache, t):
    # the attention QKV projection: 128 heads x 128 over 4 slices
    fs = 128 * 128 // 4
    _compile(
        split_stack_gemm, one_chip,
        ((t, D), BF16), ((1, D, fs), BF16), ((3, D, fs), BF16),
    )


@pytest.mark.parametrize("t", [8, 2048])
def test_split_reduce_gemm_compiles(one_chip, no_persistent_cache, t):
    # the attention output projection, row-split over 4 slices
    fs = 128 * 128 // 4
    _compile(
        split_reduce_gemm, one_chip,
        ((4, t, fs), BF16), ((1, fs, D), BF16), ((3, fs, D), BF16),
    )
