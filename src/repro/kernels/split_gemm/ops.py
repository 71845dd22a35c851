"""jit'd public wrappers for the split-weight kernels.

On CPU (this container) the Pallas kernels execute in interpret mode; on
a real TPU backend they compile to Mosaic (``interpret`` defaults from
the backend — pass ``interpret=...`` explicitly to override).

Every engine-facing op takes ``impl``:

- ``"pallas"`` — the fused §4.2 kernels (the TPU inference hot path).
- ``"jnp"``    — a differentiable formulation that computes each bank's
  slice separately and combines the *outputs* (activations) — never the
  weight banks. Grad-through-gather for the train shapes routes here,
  since ``pallas_call`` has no registered VJP.
- ``None``     — "pallas" (the kernel itself; bare calls are kernel
  coverage). The ENGINE never passes None for the dense family — it
  resolves the impl through ``default_dense_impl(phase)`` below.

Both impls honor the same contract: slices/experts [0, n_local) read the
local bank, [n_local, n) the remote bank; no merged weight buffer is ever
materialized.

Impl policy
-----------
``split_swiglu`` (the MoE grouped op, a few layers per model) defaults to
pallas for inference everywhere — interpret mode on CPU doubles as
engine-level kernel coverage. The *dense* family (``split_stack_matmul``
/ ``split_reduce_matmul`` / ``split_dense_ffn``) sits on every attention
and dense-FFN projection of every layer, so ``default_dense_impl`` picks
pallas only on a real TPU and the (equally merge-free, numerically
matching) jnp formulation elsewhere — keeping the CPU test suite's
interpret-mode cost bounded while the kernels themselves stay covered by
the dedicated interpret-mode sweeps in tests/test_kernels.py. The
single-rank MoE layer (every expert resident, nothing gathered) follows
the same policy: ``split_swiglu`` with an empty remote bank on a TPU,
the jnp grouped FFN elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.split_gemm.dense import (
    split_dense_swiglu,
    split_reduce_gemm,
    split_stack_gemm,
)
from repro.kernels.split_gemm.split_gemm import (
    _cast,
    split_grouped_gemm,
    split_grouped_swiglu,
    split_grouped_swiglu_demand,
)
from repro.kernels.split_gemm.ref import (
    split_dense_swiglu_ref,
    split_grouped_gemm_ref,
    split_grouped_swiglu_demand_ref,
    split_grouped_swiglu_ref,
    split_reduce_gemm_ref,
    split_stack_gemm_ref,
)
from repro.models.moe import grouped_ffn


def default_dense_impl(phase: str) -> str:
    """Engine policy for the dense/attention split ops (see module doc),
    shared by the single-rank MoE layer, whose whole expert bank is
    resident."""
    if phase == "train":
        return "jnp"
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def split_gemm(x, w_local, w_remote, **kw):
    """Grouped GEMM over split expert banks. x: (E, C, D);
    w_local: (E_l, D, F); w_remote: (E-E_l, D, F) -> (E, C, F)."""
    return split_grouped_gemm(x, w_local, w_remote, **kw)


# --------------------------------------------------------------------------
# MoE grouped SwiGLU (PR 1).
# --------------------------------------------------------------------------
def split_swiglu_jnp(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r):
    """Differentiable split SwiGLU without a bank merge: per-bank grouped
    FFN over the matching expert slice of ``x``, outputs concatenated.
    The concat is over (E, C, D) activations — a factor d_ff/d_model
    smaller than the weight merge the paper eliminates — and gradients
    flow to both banks (and through any gather that produced them)."""
    e_l = wg_l.shape[0]
    y_l = grouped_ffn(x[:e_l], wg_l, wu_l, wd_l)
    y_r = grouped_ffn(x[e_l:], wg_r, wu_r, wd_r)
    return jnp.concatenate([y_l, y_r], axis=0)


def split_swiglu(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r, *, impl=None, **kw):
    """Fused split grouped SwiGLU. x: (E, C, D); gate/up banks (E_*, D, F),
    down banks (E_*, F, D) -> (E, C, D). See module docstring for impl."""
    if impl in (None, "pallas"):
        return split_grouped_swiglu(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r, **kw)
    if impl == "jnp":
        return split_swiglu_jnp(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r)
    raise ValueError(f"unknown split_swiglu impl {impl!r}")


def split_swiglu_demand_jnp(x, wg_l, wu_l, wd_l, wg_f, wu_f, wd_f, valid):
    """Differentiable demand SwiGLU without a bank merge: per-bank
    grouped FFN over the matching slice of the compact dispatch, fetched
    outputs zeroed where the budget padding's validity mask is False
    (their weights are clamped junk by contract). Gradients flow to both
    banks — and through the demand gather's take/ppermute — which is
    what lets the route-before-gather path ride the train shapes."""
    e_l = wg_l.shape[0]
    y_l = grouped_ffn(x[:e_l], wg_l, wu_l, wd_l)
    y_f = grouped_ffn(x[e_l:], wg_f, wu_f, wd_f)
    y_f = y_f * valid[:, None, None].astype(y_f.dtype)
    return jnp.concatenate([y_l, y_f], axis=0)


def split_swiglu_demand(
    x, wg_l, wu_l, wd_l, wg_f, wu_f, wd_f, valid, *, impl=None, **kw
):
    """Fused demand-fetched grouped SwiGLU. x: (E_l + E_f, C, D) compact
    dispatch; local banks (E_l, D, F)/(E_l, F, D); fetched banks
    (E_f, D, F)/(E_f, F, D) budget-padded with ``valid`` (E_f,) marking
    real rows -> (E_l + E_f, C, D)."""
    if impl in (None, "pallas"):
        return split_grouped_swiglu_demand(
            x, wg_l, wu_l, wd_l, wg_f, wu_f, wd_f, valid, **kw
        )
    if impl == "jnp":
        return split_swiglu_demand_jnp(
            x, wg_l, wu_l, wd_l, wg_f, wu_f, wd_f, valid
        )
    raise ValueError(f"unknown split_swiglu_demand impl {impl!r}")


# --------------------------------------------------------------------------
# Dense stacked-slice family (attention QKV/O, dense FFN).
# --------------------------------------------------------------------------
def split_stack_matmul_jnp(x, w_local, w_remote):
    """Column-split projection without a bank merge: per-bank stacked
    einsum, outputs concatenated over the (S, T, Fs) *activation* axis."""
    y_l = jnp.einsum("td,sdf->stf", x, _cast(w_local, x))
    y_r = jnp.einsum("td,sdf->stf", x, _cast(w_remote, x))
    return jnp.concatenate([y_l, y_r], axis=0)


def split_stack_matmul(x, w_local, w_remote, *, impl=None, **kw):
    """Shared-activation stacked projection over split banks.
    x: (T, D); banks (S_l, D, Fs)/(S-S_l, D, Fs) -> (S, T, Fs), slice
    order = bank order (local first, then rotated remote)."""
    if impl in (None, "pallas"):
        return split_stack_gemm(x, w_local, w_remote, **kw)
    if impl == "jnp":
        return split_stack_matmul_jnp(x, w_local, w_remote)
    raise ValueError(f"unknown split_stack_matmul impl {impl!r}")


def split_reduce_matmul_jnp(x, w_local, w_remote):
    """Row-split reduction without a bank merge: per-bank contraction of
    the matching slice range, partial sums added (order-independent)."""
    s_l = w_local.shape[0]
    y_l = jnp.einsum("stf,sfd->td", x[:s_l], _cast(w_local, x))
    y_r = jnp.einsum("stf,sfd->td", x[s_l:], _cast(w_remote, x))
    return y_l + y_r


def split_reduce_matmul(x, w_local, w_remote, *, impl=None, **kw):
    """Per-slice reduction over split banks. x: (S, T, Fs); banks
    (S_l, Fs, D)/(S-S_l, Fs, D) -> (T, D) = sum_s x[s] @ w[s]."""
    if impl in (None, "pallas"):
        return split_reduce_gemm(x, w_local, w_remote, **kw)
    if impl == "jnp":
        return split_reduce_matmul_jnp(x, w_local, w_remote)
    raise ValueError(f"unknown split_reduce_matmul impl {impl!r}")


def split_dense_ffn_jnp(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r):
    """Differentiable dense split SwiGLU without a bank merge: per-bank
    stacked SwiGLU (the same math ``execution._ffn_full`` runs), partial
    sums added. Slice order cancels in the sum, so the rotated remote
    bank never needs canonicalizing."""
    def part(wg, wu, wd):
        h = jax.nn.silu(
            jnp.einsum("td,sdf->tsf", x, _cast(wg, x))
        ) * jnp.einsum("td,sdf->tsf", x, _cast(wu, x))
        return jnp.einsum("tsf,sfd->td", h, _cast(wd, x))

    return part(wg_l, wu_l, wd_l) + part(wg_r, wu_r, wd_r)


def split_dense_ffn(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r, *, impl=None, **kw):
    """Fused dense-FFN SwiGLU over split banks. x: (T, D); gate/up banks
    (S_*, D, Fs), down banks (S_*, Fs, D) -> (T, D)."""
    if impl in (None, "pallas"):
        return split_dense_swiglu(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r, **kw)
    if impl == "jnp":
        return split_dense_ffn_jnp(x, wg_l, wu_l, wd_l, wg_r, wu_r, wd_r)
    raise ValueError(f"unknown split_dense_ffn impl {impl!r}")


__all__ = [
    "default_dense_impl",
    "split_gemm",
    "split_grouped_gemm",
    "split_grouped_gemm_ref",
    "split_swiglu",
    "split_swiglu_jnp",
    "split_swiglu_demand",
    "split_swiglu_demand_jnp",
    "split_grouped_swiglu",
    "split_grouped_swiglu_demand",
    "split_grouped_swiglu_demand_ref",
    "split_grouped_swiglu_ref",
    "split_stack_gemm",
    "split_stack_gemm_ref",
    "split_stack_matmul",
    "split_stack_matmul_jnp",
    "split_reduce_gemm",
    "split_reduce_gemm_ref",
    "split_reduce_matmul",
    "split_reduce_matmul_jnp",
    "split_dense_swiglu",
    "split_dense_swiglu_ref",
    "split_dense_ffn",
    "split_dense_ffn_jnp",
]
