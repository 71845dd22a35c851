"""Split-weight *dense* matmul Pallas kernels (paper §4.2 generalized).

PR 1 applied the split-bank technique to MoE expert banks only. These
kernels extend it to every stacked-storage dense weight family the DWDP
prefetch pipeline gathers — attention QKV/O projections and dense-FFN
("virtual expert") projections — so the engine's ``weight_layout="split"``
mode never materializes a merged ``(S, D, F/S)`` weight buffer for *any*
gathered family.

All three kernels consume the ``(resident shard, rotated remote bank)``
pair produced by ``prefetch.gather_split_bank``: slices ``[0, S_l)`` read
the local bank, ``[S_l, S)`` the remote bank, selected per grid step with
``pl.when`` on the slice coordinate over predicated (clamped) BlockSpecs —
the same two-operand streaming structure as ``split_grouped_gemm``, with
no merge copy anywhere.

- ``split_stack_gemm``: column-split projection. One shared activation
  ``x (T, D)`` against S stacked slices ``(S_*, D, Fs)`` -> ``(S, T, Fs)``
  (one output block per slice; the engine canonicalizes slice order with
  an activation-level roll — weights are never reordered).
- ``split_reduce_gemm``: row-split projection. Per-slice activations
  ``x (S, T, Fs)`` against ``(S_*, Fs, D)`` -> ``(T, D)`` accumulating the
  slice contributions in a fp32 VMEM tile (order-independent, so the
  rotated bank order needs no fix-up at all).
- ``split_dense_swiglu``: the fused dense FFN. Because SwiGLU slices are
  independent through the elementwise stage and summed by the down
  projection, the whole stacked FFN is ``y = sum_s swiglu_s(x)`` — gate
  and up stream both banks predicated, silu-mul runs on the fp32
  accumulators, and the down GEMM accumulates straight into a per-token-
  block output accumulator. Slice order cancels in the sum, so the dense
  split path needs no roll whatsoever.

Block sizes auto-select per dimension exactly like the grouped kernels
(largest lane-friendly divisor for weights; the token dim one block up to
``block_c`` and zero-padded to a multiple of it beyond), so decode-scale
and odd prompt-length token counts stream correctly. The fused SwiGLU also shares the grouped
kernel's down-projection output-dim blocking (``block_o``, auto-selected
so that the accumulators and the double-buffered tiles fit the step's
VMEM budget), so d_model beyond the single-pass envelope lowers on the
dense path too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.split_gemm.split_gemm import (
    _auto_blocks,
    _cast,
    _dummy_banks,
    _pad_rows,
    _pick_block,
    _row_block,
)


# ==========================================================================
# Column-split stacked GEMM: shared x, one output block per slice.
# ==========================================================================
def _stack_kernel(n_local: int, x_ref, wl_ref, wr_ref, o_ref, acc_ref):
    s = pl.program_id(0)
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bc, bd)

    @pl.when(s < n_local)
    def _local():
        acc_ref[...] += jnp.dot(
            x, _cast(wl_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(s >= n_local)
    def _remote():
        acc_ref[...] += jnp.dot(
            x, _cast(wr_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(kd == pl.num_programs(3) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_f", "block_d", "interpret"),
)
def split_stack_gemm(
    x: jax.Array,         # (T, D) shared activations
    w_local: jax.Array,   # (S_l, D, Fs) resident slices
    w_remote: jax.Array,  # (S - S_l, D, Fs) rotated remote slices
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Column-split stacked projection over split banks: (T, D) -> (S, T, Fs)."""
    t, d = x.shape
    s_l = w_local.shape[0]
    s_r = w_remote.shape[0]
    s = s_l + s_r
    f = (w_local if s_l else w_remote).shape[2]
    w_local, w_remote = _dummy_banks(s_l, s_r, w_local, w_remote, (1, d, f))
    n_wl = w_local.shape[0]
    n_wr = w_remote.shape[0]

    t_pad, bc = _row_block(t, block_c)
    x = _pad_rows(x, 0, t_pad)
    bf = _pick_block(f, block_f)
    bd = _pick_block(d, block_d)

    grid = (s, t_pad // bc, f // bf, d // bd)

    def x_map(si, ci, fi, di):
        return (ci, di)

    def wl_map(si, ci, fi, di):
        return (jnp.clip(si, 0, n_wl - 1), di, fi)

    def wr_map(si, ci, fi, di):
        return (jnp.clip(si - s_l, 0, n_wr - 1), di, fi)

    def o_map(si, ci, fi, di):
        return (si, ci, fi)

    return pl.pallas_call(
        functools.partial(_stack_kernel, s_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bd), x_map),
            pl.BlockSpec((1, bd, bf), wl_map),
            pl.BlockSpec((1, bd, bf), wr_map),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), o_map),
        out_shape=jax.ShapeDtypeStruct((s, t_pad, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w_local, w_remote)[:, :t]


# ==========================================================================
# Row-split reduce GEMM: per-slice x, contributions summed over slices.
# ==========================================================================
def _reduce_kernel(n_local: int, x_ref, wl_ref, wr_ref, o_ref, acc_ref):
    si = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(jnp.logical_and(si == 0, ki == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # (bc, bk)

    @pl.when(si < n_local)
    def _local():
        acc_ref[...] += jnp.dot(
            x, _cast(wl_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(si >= n_local)
    def _remote():
        acc_ref[...] += jnp.dot(
            x, _cast(wr_ref[0], x), preferred_element_type=jnp.float32
        )

    last = jnp.logical_and(
        si == pl.num_programs(2) - 1, ki == pl.num_programs(3) - 1
    )

    @pl.when(last)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_o", "block_k", "interpret"),
)
def split_reduce_gemm(
    x: jax.Array,         # (S, T, Fs) per-slice activations
    w_local: jax.Array,   # (S_l, Fs, D)
    w_remote: jax.Array,  # (S - S_l, Fs, D)
    *,
    block_c: int = 128,
    block_o: int = 512,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Row-split reduction over split banks: sum_s x[s] @ w[s] -> (T, D).

    The slice sum is order-independent, so the rotated remote bank order
    never needs canonicalizing on this side."""
    s, t, f = x.shape
    s_l = w_local.shape[0]
    s_r = w_remote.shape[0]
    assert s_l + s_r == s, (s_l, s_r, s)
    d = (w_local if s_l else w_remote).shape[2]
    w_local, w_remote = _dummy_banks(s_l, s_r, w_local, w_remote, (1, f, d))
    n_wl = w_local.shape[0]
    n_wr = w_remote.shape[0]

    t_pad, bc = _row_block(t, block_c)
    x = _pad_rows(x, 1, t_pad)
    bo = _pick_block(d, block_o)
    bk = _pick_block(f, block_k)

    grid = (t_pad // bc, d // bo, s, f // bk)

    def x_map(ci, oi, si, ki):
        return (si, ci, ki)

    def wl_map(ci, oi, si, ki):
        return (jnp.clip(si, 0, n_wl - 1), ki, oi)

    def wr_map(ci, oi, si, ki):
        return (jnp.clip(si - s_l, 0, n_wr - 1), ki, oi)

    def o_map(ci, oi, si, ki):
        return (ci, oi)

    return pl.pallas_call(
        functools.partial(_reduce_kernel, s_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), x_map),
            pl.BlockSpec((1, bk, bo), wl_map),
            pl.BlockSpec((1, bk, bo), wr_map),
        ],
        out_specs=pl.BlockSpec((bc, bo), o_map),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bo), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w_local, w_remote)[:t]


# ==========================================================================
# Fused dense split SwiGLU: y = sum_s swiglu_s(x), both banks predicated.
# ==========================================================================
def _dense_swiglu_kernel(
    n_local: int,
    x_ref, gl_ref, ul_ref, dl_ref, gr_ref, ur_ref, dr_ref,
    o_ref,
    acc_g, acc_u, acc_y,
):
    si = pl.program_id(2)
    fi = pl.program_id(3)
    di = pl.program_id(4)
    last_s = si == pl.num_programs(2) - 1
    last_f = fi == pl.num_programs(3) - 1
    last_d = di == pl.num_programs(4) - 1
    is_local = si < n_local

    @pl.when(jnp.logical_and(si == 0, jnp.logical_and(fi == 0, di == 0)))
    def _init_y():
        acc_y[...] = jnp.zeros_like(acc_y)

    @pl.when(di == 0)
    def _init_gu():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...]  # (bc, bd)

    @pl.when(is_local)
    def _first_local():
        acc_g[...] += jnp.dot(
            x, _cast(gl_ref[0], x), preferred_element_type=jnp.float32
        )
        acc_u[...] += jnp.dot(
            x, _cast(ul_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_not(is_local))
    def _first_remote():
        acc_g[...] += jnp.dot(
            x, _cast(gr_ref[0], x), preferred_element_type=jnp.float32
        )
        acc_u[...] += jnp.dot(
            x, _cast(ur_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(last_d, is_local))
    def _down_local():
        h = (jax.nn.silu(acc_g[...]) * acc_u[...]).astype(x.dtype)
        acc_y[...] += jnp.dot(
            h, _cast(dl_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(last_d, jnp.logical_not(is_local)))
    def _down_remote():
        h = (jax.nn.silu(acc_g[...]) * acc_u[...]).astype(x.dtype)
        acc_y[...] += jnp.dot(
            h, _cast(dr_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(last_s, jnp.logical_and(last_f, last_d)))
    def _flush():
        o_ref[...] = acc_y[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_f", "block_d", "block_o", "interpret"),
)
def split_dense_swiglu(
    x: jax.Array,          # (T, D)
    wg_local: jax.Array,   # (S_l, D, Fs)
    wu_local: jax.Array,   # (S_l, D, Fs)
    wd_local: jax.Array,   # (S_l, Fs, D)
    wg_remote: jax.Array,  # (S - S_l, D, Fs)
    wu_remote: jax.Array,  # (S - S_l, D, Fs)
    wd_remote: jax.Array,  # (S - S_l, Fs, D)
    *,
    block_c: int = 128,
    block_f: int = 256,
    block_d: int = 512,
    block_o: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused stacked-slice SwiGLU over split banks: (T, D) -> (T, D).

    Slices [0, S_l) read the local bank, [S_l, S) the remote bank; the
    (T, Fs) hidden activations never round-trip HBM and the slice sum
    makes bank order irrelevant. ``block_o`` blocks the down
    projection's *output* dim (ported from the grouped kernel) so
    d_model beyond the VMEM accumulator budget still lowers: with
    n_o = D/block_o output blocks the gate/up stages are recomputed once
    per block (the standard recompute-vs-residency trade), and
    ``block_o=None`` auto-selects with the grouped kernel's
    ``_auto_blocks`` — the full D (the single-pass (bc, D) schedule)
    whenever the step's tiles fit its VMEM budget, the largest fitting
    divisor otherwise."""
    t, d = x.shape
    s_l = wg_local.shape[0]
    s_r = wg_remote.shape[0]
    s = s_l + s_r
    f = (wg_local if s_l else wg_remote).shape[2]
    wg_local, wg_remote = _dummy_banks(s_l, s_r, wg_local, wg_remote, (1, d, f))
    wu_local, wu_remote = _dummy_banks(s_l, s_r, wu_local, wu_remote, (1, d, f))
    wd_local, wd_remote = _dummy_banks(s_l, s_r, wd_local, wd_remote, (1, f, d))
    n_wl = wg_local.shape[0]
    n_wr = wg_remote.shape[0]

    t_pad, bc = _row_block(t, block_c)
    x = _pad_rows(x, 0, t_pad)
    bf = _pick_block(f, block_f)
    bd = _pick_block(d, block_d)
    if block_o is None:
        bf, bo = _auto_blocks(
            d, f, bc, bf, bd, x.dtype.itemsize,
            max(wg_local.dtype.itemsize, wg_remote.dtype.itemsize),
        )
    else:
        bo = _pick_block(d, block_o)

    grid = (t_pad // bc, d // bo, s, f // bf, d // bd)

    def x_map(ci, oi, si, fi, di):
        return (ci, di)

    def up_l_map(ci, oi, si, fi, di):
        return (jnp.clip(si, 0, n_wl - 1), di, fi)

    def up_r_map(ci, oi, si, fi, di):
        return (jnp.clip(si - s_l, 0, n_wr - 1), di, fi)

    def down_l_map(ci, oi, si, fi, di):
        return (jnp.clip(si, 0, n_wl - 1), fi, oi)

    def down_r_map(ci, oi, si, fi, di):
        return (jnp.clip(si - s_l, 0, n_wr - 1), fi, oi)

    def o_map(ci, oi, si, fi, di):
        return (ci, oi)

    return pl.pallas_call(
        functools.partial(_dense_swiglu_kernel, s_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bd), x_map),
            pl.BlockSpec((1, bd, bf), up_l_map),
            pl.BlockSpec((1, bd, bf), up_l_map),
            pl.BlockSpec((1, bf, bo), down_l_map),
            pl.BlockSpec((1, bd, bf), up_r_map),
            pl.BlockSpec((1, bd, bf), up_r_map),
            pl.BlockSpec((1, bf, bo), down_r_map),
        ],
        out_specs=pl.BlockSpec((bc, bo), o_map),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bo), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, wg_local, wu_local, wd_local, wg_remote, wu_remote, wd_remote)[:t]
