"""Blockwise causal/sliding-window GQA flash attention (Pallas TPU).

Grid: (B*Kh, rep, Sq/bq, Sk/bk) — the KV-block loop is the innermost grid
dimension so the online-softmax state (m, l, acc) carries across it in
VMEM scratch. Block sizes are MXU-aligned (multiples of 128 on the lane
dim). The causal + sliding-window mask is applied per tile from absolute
positions, so the same kernel serves the full-attention archs and the
local-attention layers of gemma3 / recurrentgemma.

This is the context-phase compute window that hides DWDP's weight
prefetch — on real hardware it and the grouped GEMM dominate the layer
time (paper Table 1: Attention + GroupedGEMM ~= 56% of DWDP4 iteration).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(scale, window, q_offset, sk_valid, q_ref, k_ref, v_ref, o_ref,
             m_ref, l_ref, acc_ref):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0] * scale                   # (bq, hd)
    k = k_ref[0]                              # (bk, hd)
    v = v_ref[0]
    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0
    )
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (k_pos <= q_pos) & (k_pos < sk_valid)
    if window:
        mask &= q_pos - k_pos < window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, Kh, hd)
    v: jax.Array,
    *,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    rep = h // kh
    scale = 1.0 / math.sqrt(hd)

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    sk_pad = -(-sk // bk) * bk
    if sq % bq:
        raise ValueError(f"Sq={sq} must divide block_q={bq}")
    if sk_pad != sk:
        pad = sk_pad - sk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # (B, S, H, hd) -> (B*Kh, rep, S, hd) so GQA groups share a KV block
    qx = (
        q.reshape(b, sq, kh, rep, hd)
        .transpose(0, 2, 3, 1, 4)
        .reshape(b * kh, rep, sq, hd)
    )
    kx = k.transpose(0, 2, 1, 3).reshape(b * kh, sk_pad, hd)
    vx = v.transpose(0, 2, 1, 3).reshape(b * kh, sk_pad, hd)

    grid = (b * kh, rep, sq // bq, sk_pad // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale, window, q_offset, sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda g, r, qi, kj: (g, r, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda g, r, qi, kj: (g, kj, 0)),
            pl.BlockSpec((1, bk, hd), lambda g, r, qi, kj: (g, kj, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, hd), lambda g, r, qi, kj: (g, r, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b * kh, rep, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qx, kx, vx)

    return (
        out.reshape(b, kh, rep, sq, hd)
        .transpose(0, 3, 1, 2, 4)
        .reshape(b, sq, h, hd)
    )
