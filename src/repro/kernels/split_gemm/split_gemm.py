"""Split-weight grouped GEMM / grouped SwiGLU Pallas kernels (paper §4.2,
TPU adaptation).

The CUDA original extends a CuTeDSL grouped GEMM with TensorList inputs so
the kernel can read each expert's weights from either the resident-local
bank or the prefetched-remote bank. On TPU the analogous structure is two
HBM operands with *predicated BlockSpec streaming*: both banks are blocked
into VMEM tiles by the same grid, their index_maps clamp to a valid tile,
and the kernel body selects the correct tile with ``pl.when`` on the
expert coordinate — so only the selected bank's tile participates in the
MXU matmul and no merged contiguous buffer ever exists in HBM.

Three kernels:

- ``split_grouped_gemm``: one GEMM stage (kept as the minimal §4.2 unit).
- ``split_grouped_swiglu``: the full MoE FFN fused into one kernel —
  gate and up GEMMs stream both banks predicated, silu·mul runs on the
  fp32 VMEM accumulators between stages, and the down GEMM accumulates
  straight into a per-(expert, token-block) fp32 output accumulator. The
  intermediate (E, C, F) hidden activation never round-trips HBM.
- ``split_grouped_swiglu_demand``: the on-demand variant. The remote
  operand is the *compacted* demand-fetched bank — ``(budget, D, F)``
  rows of exactly the routing-activated experts, padded to the static
  budget — plus a per-row validity mask held whole in SMEM. Invalid
  (padding) rows hold clamped junk weights; the mask predicates every
  MXU stage for them, so their output blocks flush the zero-initialized
  accumulator and the padding costs no FLOPs.

Grid: (E, C/bc, F/bf, D/bd) for the single GEMM and
(E, C/bc, D/bo, F/bf, D/bd) for the fused SwiGLU, with fp32 VMEM
accumulator scratch; the reduction loop is the innermost grid dimension
so the accumulator carries across it (standard Pallas matmul pipelining).
The SwiGLU's D/bo coordinate blocks the down-projection *output* dim so
d_model beyond the VMEM accumulator budget lowers (bo = D, i.e. a single
output block, whenever it fits — gate/up recompute only kicks in when
blocking does). Weight block sizes are auto-selected per dimension
(largest lane-friendly divisor, a single block where none divides). The
capacity (token) dim is one block up to ``block_c`` — decode-scale MoE
capacities, which ``capacity_for`` keeps exact below 8 — and is
zero-padded to a multiple of ``block_c`` beyond it when that does not
divide it (``_row_block``).

The dense (non-grouped) siblings — ``split_stack_gemm``,
``split_reduce_gemm``, ``split_dense_swiglu`` in ``dense.py`` — extend
the same predicated two-bank streaming to attention QKV/O and dense-FFN
projections for ``weight_layout="split"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_BLOCK_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)


def _pick_block(n: int, preferred: int) -> int:
    """Largest candidate <= preferred that divides n (fallback: n itself,
    i.e. a single unblocked step). Keeps tiles lane-aligned when possible
    without asserting 128-divisibility on the caller."""
    if preferred >= n:
        return n
    if n % preferred == 0:
        return preferred
    for b in _BLOCK_CANDIDATES:
        if b <= preferred and n % b == 0:
            return b
    return n


def _row_block(n: int, preferred: int) -> tuple[int, int]:
    """(padded rows, block) for a token or capacity dim. Rows past
    ``preferred`` that it does not divide are zero-padded up to a multiple
    of it: a single (n, ...) block of a long odd dim overruns VMEM, and a
    small divisor re-streams every weight tile once per row block."""
    if n <= preferred or n % preferred == 0:
        return n, min(n, preferred)
    return -(-n // preferred) * preferred, preferred


def _pad_rows(x: jax.Array, axis: int, n: int) -> jax.Array:
    """``x`` zero-padded along ``axis`` to ``n`` rows. Zero rows stay
    zero through every kernel here and are sliced off the output."""
    if x.shape[axis] == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad)


def _cast(w, like):
    """fp8-stored bank tiles dequantize to the activation dtype on use."""
    return w.astype(like.dtype) if w.dtype != like.dtype else w


def _dummy_banks(e_l, e_r, w_local, w_remote, shape):
    """Empty banks (fully-local or fully-remote layers) still need a
    streamable dummy tile; the expert predicate keeps it out of the MXU."""
    if e_l == 0:
        w_local = jnp.zeros(shape, w_remote.dtype)
    if e_r == 0:
        w_remote = jnp.zeros(shape, w_local.dtype)
    return w_local, w_remote


# ==========================================================================
# Single predicated GEMM (the minimal §4.2 unit).
# ==========================================================================
def _gemm_kernel(n_local: int, x_ref, wl_ref, wr_ref, o_ref, acc_ref):
    e = pl.program_id(0)
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # (bc, bd)

    @pl.when(e < n_local)
    def _local():
        acc_ref[...] += jnp.dot(
            x, _cast(wl_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(e >= n_local)
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
def split_grouped_gemm(
    x: jax.Array,         # (E, C, D)
    w_local: jax.Array,   # (E_l, D, F)
    w_remote: jax.Array,  # (E - E_l, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    e, c, d = x.shape
    e_l, _, f = w_local.shape
    e_r = w_remote.shape[0]
    assert e_l + e_r == e, (e_l, e_r, e)
    w_local, w_remote = _dummy_banks(e_l, e_r, w_local, w_remote, (1, d, f))
    n_wl = w_local.shape[0]
    n_wr = w_remote.shape[0]

    c_pad, bc = _row_block(c, block_c)
    x = _pad_rows(x, 1, c_pad)
    bf = _pick_block(f, block_f)
    bd = _pick_block(d, block_d)

    grid = (e, c_pad // bc, f // bf, d // bd)

    def x_map(ei, ci, fi, di):
        return (ei, ci, di)

    def wl_map(ei, ci, fi, di):
        # clamp: when this expert is remote, stream tile 0 (discarded)
        return (jnp.clip(ei, 0, n_wl - 1), di, fi)

    def wr_map(ei, ci, fi, di):
        return (jnp.clip(ei - e_l, 0, n_wr - 1), di, fi)

    def o_map(ei, ci, fi, di):
        return (ei, ci, fi)

    return pl.pallas_call(
        functools.partial(_gemm_kernel, e_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), x_map),
            pl.BlockSpec((1, bd, bf), wl_map),
            pl.BlockSpec((1, bd, bf), wr_map),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), o_map),
        out_shape=jax.ShapeDtypeStruct((e, c_pad, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w_local, w_remote)[:, :c]


# ==========================================================================
# Fused split grouped SwiGLU: gate/up/down over the two banks.
# ==========================================================================
def _swiglu_kernel(
    n_local: int,
    x_ref, gl_ref, ul_ref, dl_ref, gr_ref, ur_ref, dr_ref,
    o_ref,
    acc_g, acc_u, acc_y,
):
    e = pl.program_id(0)
    fi = pl.program_id(3)
    di = pl.program_id(4)
    last_f = fi == pl.num_programs(3) - 1
    last_d = di == pl.num_programs(4) - 1
    is_local = e < n_local

    @pl.when(jnp.logical_and(fi == 0, di == 0))
    def _init_y():
        acc_y[...] = jnp.zeros_like(acc_y)

    @pl.when(di == 0)
    def _init_gu():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[0]  # (bc, bd)

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

    # gate/up tiles complete at the last D step: fuse silu·mul on the fp32
    # accumulators and push this F-tile through the matching down bank.
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

    @pl.when(jnp.logical_and(last_f, last_d))
    def _flush():
        o_ref[0] = acc_y[...].astype(o_ref.dtype)


# VMEM budget of one fused SwiGLU grid step: the fp32 accumulators, the
# f32 results of its dots, and every streamed tile, double-buffered by
# the Pallas pipeline. 10 MiB leaves Mosaic headroom under the 16 MiB
# default scoped-VMEM limit of TPU v4/v5e/v5p. When the unblocked
# (bc, D) down projection does not fit, the gate/up F tile shrinks to
# 128 lanes first (no recompute), then the down projection's output dim
# is blocked.
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def _block_o_fit(d: int, bc: int, bf: int, bd: int,
                 x_bytes: int, w_bytes: int) -> int:
    """Largest output block whose step fits ``_VMEM_BUDGET_BYTES``."""
    fixed = (
        2 * bc * bf * 4                     # gate + up fp32 accumulators
        + 3 * bc * bf * 4                   # gate/up dot results, silu*up
        + 2 * bc * bd * x_bytes             # x tile
        + 2 * 4 * bd * bf * w_bytes         # gate + up tiles, both banks
    )
    per_col = (
        2 * bc * 4                          # y accumulator + down dot
        + 2 * 2 * bf * w_bytes              # down tiles, both banks
        + 2 * bc * x_bytes                  # output tile
    )
    limit = max((_VMEM_BUDGET_BYTES - fixed) // per_col, 128)
    return _pick_block(d, int(limit))


def _auto_blocks(d: int, f: int, bc: int, bf: int, bd: int,
                 x_bytes: int, w_bytes: int) -> tuple[int, int]:
    """(bf, bo) for the fused SwiGLU kernels: the caller's F tile and the
    full output dim when they fit, else a 128-lane F tile, else the
    largest output block that fits (gate/up then recompute per block)."""
    bo = _block_o_fit(d, bc, bf, bd, x_bytes, w_bytes)
    if bo < d and bf > 128 and f % 128 == 0:
        bf = 128
        bo = _block_o_fit(d, bc, bf, bd, x_bytes, w_bytes)
    return bf, bo


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_f", "block_d", "block_o", "interpret"),
)
def split_grouped_swiglu(
    x: jax.Array,          # (E, C, D)
    wg_local: jax.Array,   # (E_l, D, F)
    wu_local: jax.Array,   # (E_l, D, F)
    wd_local: jax.Array,   # (E_l, F, D)
    wg_remote: jax.Array,  # (E - E_l, D, F)
    wu_remote: jax.Array,  # (E - E_l, D, F)
    wd_remote: jax.Array,  # (E - E_l, F, D)
    *,
    block_c: int = 128,
    block_f: int = 256,
    block_d: int = 512,
    block_o: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused per-expert SwiGLU over split weight banks: (E, C, D) -> (E, C, D).

    Experts [0, E_l) read the local bank, [E_l, E) the remote bank. The
    down-projection accumulates into a (bc, block_o) fp32 scratch.
    ``block_o`` blocks the down projection's *output* dim so d_model
    beyond the VMEM accumulator budget still lowers: with n_o = D/block_o
    output blocks the gate/up stages are recomputed once per block (the
    standard recompute-vs-residency trade), and ``block_o=None``
    auto-selects (:func:`_auto_blocks`) — the full D (the single-pass
    schedule) whenever the step's tiles fit ``_VMEM_BUDGET_BYTES``, the
    largest fitting divisor otherwise.
    """
    e, c, d = x.shape
    e_l, _, f = wg_local.shape
    e_r = wg_remote.shape[0]
    assert e_l + e_r == e, (e_l, e_r, e)
    wg_local, wg_remote = _dummy_banks(e_l, e_r, wg_local, wg_remote, (1, d, f))
    wu_local, wu_remote = _dummy_banks(e_l, e_r, wu_local, wu_remote, (1, d, f))
    wd_local, wd_remote = _dummy_banks(e_l, e_r, wd_local, wd_remote, (1, f, d))
    n_wl = wg_local.shape[0]
    n_wr = wg_remote.shape[0]

    c_pad, bc = _row_block(c, block_c)
    x = _pad_rows(x, 1, c_pad)
    bf = _pick_block(f, block_f)
    bd = _pick_block(d, block_d)
    if block_o is None:
        bf, bo = _auto_blocks(
            d, f, bc, bf, bd, x.dtype.itemsize,
            max(wg_local.dtype.itemsize, wg_remote.dtype.itemsize),
        )
    else:
        bo = _pick_block(d, block_o)

    grid = (e, c_pad // bc, d // bo, f // bf, d // bd)

    def x_map(ei, ci, oi, fi, di):
        return (ei, ci, di)

    def up_l_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei, 0, n_wl - 1), di, fi)

    def up_r_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei - e_l, 0, n_wr - 1), di, fi)

    def down_l_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei, 0, n_wl - 1), fi, oi)

    def down_r_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei - e_l, 0, n_wr - 1), fi, oi)

    def o_map(ei, ci, oi, fi, di):
        return (ei, ci, oi)

    return pl.pallas_call(
        functools.partial(_swiglu_kernel, e_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), x_map),
            pl.BlockSpec((1, bd, bf), up_l_map),
            pl.BlockSpec((1, bd, bf), up_l_map),
            pl.BlockSpec((1, bf, bo), down_l_map),
            pl.BlockSpec((1, bd, bf), up_r_map),
            pl.BlockSpec((1, bd, bf), up_r_map),
            pl.BlockSpec((1, bf, bo), down_r_map),
        ],
        out_specs=pl.BlockSpec((1, bc, bo), o_map),
        out_shape=jax.ShapeDtypeStruct((e, c_pad, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bo), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, wg_local, wu_local, wd_local, wg_remote, wu_remote, wd_remote)[:, :c]


# ==========================================================================
# Demand-fetched split SwiGLU: compacted fetched bank, validity-predicated.
# ==========================================================================
def _swiglu_demand_kernel(
    n_local: int,
    x_ref, v_ref, gl_ref, ul_ref, dl_ref, gf_ref, uf_ref, df_ref,
    o_ref,
    acc_g, acc_u, acc_y,
):
    e = pl.program_id(0)
    fi = pl.program_id(3)
    di = pl.program_id(4)
    last_f = fi == pl.num_programs(3) - 1
    last_d = di == pl.num_programs(4) - 1
    is_local = e < n_local
    # fetched rows past the requester's valid count are clamped junk: the
    # mask keeps them off the MXU entirely, so the budget padding costs
    # no FLOPs and their output blocks flush the zeroed accumulator.
    row = jnp.clip(e - n_local, 0, v_ref.shape[0] - 1)
    is_fetched = jnp.logical_and(jnp.logical_not(is_local), v_ref[row] != 0)

    @pl.when(jnp.logical_and(fi == 0, di == 0))
    def _init_y():
        acc_y[...] = jnp.zeros_like(acc_y)

    @pl.when(di == 0)
    def _init_gu():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[0]  # (bc, bd)

    @pl.when(is_local)
    def _first_local():
        acc_g[...] += jnp.dot(
            x, _cast(gl_ref[0], x), preferred_element_type=jnp.float32
        )
        acc_u[...] += jnp.dot(
            x, _cast(ul_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(is_fetched)
    def _first_fetched():
        acc_g[...] += jnp.dot(
            x, _cast(gf_ref[0], x), preferred_element_type=jnp.float32
        )
        acc_u[...] += jnp.dot(
            x, _cast(uf_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(last_d, is_local))
    def _down_local():
        h = (jax.nn.silu(acc_g[...]) * acc_u[...]).astype(x.dtype)
        acc_y[...] += jnp.dot(
            h, _cast(dl_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(last_d, is_fetched))
    def _down_fetched():
        h = (jax.nn.silu(acc_g[...]) * acc_u[...]).astype(x.dtype)
        acc_y[...] += jnp.dot(
            h, _cast(df_ref[0], x), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(last_f, last_d))
    def _flush():
        o_ref[0] = acc_y[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_f", "block_d", "block_o", "interpret"),
)
def split_grouped_swiglu_demand(
    x: jax.Array,           # (E_l + E_f, C, D) compact dispatch batches
    wg_local: jax.Array,    # (E_l, D, F) resident bank
    wu_local: jax.Array,
    wd_local: jax.Array,    # (E_l, F, D)
    wg_fetched: jax.Array,  # (E_f, D, F) demand-fetched (budget-padded)
    wu_fetched: jax.Array,
    wd_fetched: jax.Array,  # (E_f, F, D)
    valid: jax.Array,       # (E_f,) bool/int — False rows are padding
    *,
    block_c: int = 128,
    block_f: int = 256,
    block_d: int = 512,
    block_o: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused SwiGLU over the (resident, demand-fetched) bank pair:
    (E_l + E_f, C, D) -> (E_l + E_f, C, D).

    Identical streaming structure to :func:`split_grouped_swiglu` —
    same grid, same accumulators, same auto block selection, so a
    routed expert's (C, D) block computes bit-identically to the
    all-fetch split path — plus the per-row validity mask (whole in
    SMEM) predicating every MXU stage of the fetched bank. No buffer wider
    than ``E_l + E_f`` experts exists anywhere."""
    e, c, d = x.shape
    e_l, _, f = wg_local.shape
    e_f = wg_fetched.shape[0]
    assert e_l + e_f == e, (e_l, e_f, e)
    assert valid.shape == (e_f,), (valid.shape, e_f)
    wg_local, wg_fetched = _dummy_banks(e_l, e_f, wg_local, wg_fetched, (1, d, f))
    wu_local, wu_fetched = _dummy_banks(e_l, e_f, wu_local, wu_fetched, (1, d, f))
    wd_local, wd_fetched = _dummy_banks(e_l, e_f, wd_local, wd_fetched, (1, f, d))
    n_wl = wg_local.shape[0]
    n_wf = wg_fetched.shape[0]
    v = valid.astype(jnp.int32)
    if e_f == 0:
        v = jnp.zeros((1,), jnp.int32)

    c_pad, bc = _row_block(c, block_c)
    x = _pad_rows(x, 1, c_pad)
    bf = _pick_block(f, block_f)
    bd = _pick_block(d, block_d)
    if block_o is None:
        bf, bo = _auto_blocks(
            d, f, bc, bf, bd, x.dtype.itemsize,
            max(wg_local.dtype.itemsize, wg_fetched.dtype.itemsize),
        )
    else:
        bo = _pick_block(d, block_o)

    grid = (e, c_pad // bc, d // bo, f // bf, d // bd)

    def x_map(ei, ci, oi, fi, di):
        return (ei, ci, di)

    def up_l_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei, 0, n_wl - 1), di, fi)

    def up_f_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei - e_l, 0, n_wf - 1), di, fi)

    def down_l_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei, 0, n_wl - 1), fi, oi)

    def down_f_map(ei, ci, oi, fi, di):
        return (jnp.clip(ei - e_l, 0, n_wf - 1), fi, oi)

    def o_map(ei, ci, oi, fi, di):
        return (ei, ci, oi)

    return pl.pallas_call(
        functools.partial(_swiglu_demand_kernel, e_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), x_map),
            # the whole (E_f,) mask sits in SMEM for the kernel's life:
            # a per-step (1, 1) block would break Mosaic's tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bd, bf), up_l_map),
            pl.BlockSpec((1, bd, bf), up_l_map),
            pl.BlockSpec((1, bf, bo), down_l_map),
            pl.BlockSpec((1, bd, bf), up_f_map),
            pl.BlockSpec((1, bd, bf), up_f_map),
            pl.BlockSpec((1, bf, bo), down_f_map),
        ],
        out_specs=pl.BlockSpec((1, bc, bo), o_map),
        out_shape=jax.ShapeDtypeStruct((e, c_pad, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bo), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, v, wg_local, wu_local, wd_local, wg_fetched, wu_fetched,
      wd_fetched)[:, :c]
