"""DWDP execution engine: the paper's strategy as a first-class feature.

Everything that crosses ranks lives here, inside one whole-forward
``shard_map``. The three strategies share all local math and differ only
in *what moves*:

- **dwdp**: weights move. Expert / FFN / (escalated) attention weights are
  prefetch-gathered per layer — software-pipelined one layer ahead through
  the ``lax.scan`` carry (the paper's double buffering) — or ring-rotated
  through ranks when a full layer set cannot fit HBM. Activations never
  cross ranks for the FFN path; each rank serves its own tokens end to
  end. HOW each family is gathered is a per-family decision now: the
  plan carries a ``strategy.PolicyTable`` (``ExecutionPlan.policies``)
  and every consumer here reads ``xp.policy(family, group)`` —
  ``moe_experts``, ``attn_qkv``, ``attn_out``, ``dense_ffn`` — for its
  ``(layout, fetch, transport, num_slices, budget)``. With
  ``layout == "split"`` (the default) the gather is remote-only (§4.2
  generalized): the prefetch pipeline emits a ``prefetch.SplitBank`` for
  that family, the resident shard never re-lands, the prefetched payload
  is the ``(G'-1)/G'`` remote bank, and the fused split kernels consume
  both banks directly — no merged gathered-weight buffer (``(num_padded,
  D, F)`` expert bank, ``(A, D, qd/A)`` attention stack, ``(S, D, F/S)``
  FFN stack) is ever materialized. ``layout == "merged"`` keeps the
  legacy explicit merge (one canonical contiguous landing) per family;
  multi-axis (ZeRO-wide) gathers fall back to it automatically. Because
  the table is per-family, heterogeneous plans lower into ONE forward:
  e.g. demand-fetched split MoE experts + merged-allgather attention +
  split-ring dense FFN (the mixed plan the tests assert bitwise against
  its uniform-transport reference).
- **dep**: activations move. MoE uses all-to-all dispatch/combine; dense
  layers use gather + reduce-scatter TP (the synchronizing layer-boundary
  collectives of paper Fig. 1).
- **replicated**: nothing moves (pure DP reference; only meaningful when
  the weights fit replicated).

On-demand expert fetch (``xp.policy("moe_experts").fetch == "demand"`` —
the paper's "fetching missing experts on demand") inverts the engine's layer
structure for eligible MoE layers: **route-before-gather**. The
layer-ahead double buffering assumes the gather operand is known before
the layer runs — true for whole weight families, false for the
demand-selected expert subset, which only exists once the current
layer's routing has run. So for demand-active layers ``gather_set``
excludes the expert bank from the prefetch pipeline entirely (every
other family keeps its layer-ahead double buffering), and
``_moe_apply`` runs the inverted order: route (router weights are
local — a cheap (T, D) @ (D, E) matmul), build the activated-expert
bitmap, exchange indices, then fetch exactly the activated remote
experts into a compacted ``prefetch.DemandBank`` (budget-padded). Token
dispatch is remapped through ``fetched_ids`` instead of the PR 1
rotation roll, and the validity-predicated demand kernel consumes the
(resident, fetched) banks. When the activated set overflows the static
budget, an axis-agreed flag falls back per-layer to the full remote
gather (``lax.cond`` — all ranks take the same branch), so results are
always exact and never a function of the budget.

Sequence sharding (when the batch can't cover the mesh), KV-cache decode
with psum-LSE combine, RG-LRU cross-shard fix-up, vocab-sharded heads and
ZeRO-style train gathers are all implemented here so every
(arch x shape x mesh x mode) combination lowers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import BlockKind
from repro.core import faults
from repro.core import prefetch
from repro.kernels import split_gemm as split_gemm_lib
from repro.core.placement import Placement, make_placement
from repro.core.strategy import ExecutionPlan, input_pspecs, output_pspecs, state_pspecs
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models.cache import init_decode_state
from repro.models.layers import causal_conv1d, rms_norm, apply_rope, softcap
from repro.models.recurrent import recurrent_block, rglru_parts
from repro.models.transformer import AXIS_MODEL, Geometry, LayerSig, Model
from repro.models.xlstm import mlstm_block, slstm_block

PyTree = Any
XENT_CHUNK = 512


# ==========================================================================
# Small axis helpers (all used inside shard_map).
# ==========================================================================
def _axsize(xp: ExecutionPlan, axes: tuple[str, ...]) -> int:
    return math.prod(xp.mesh_sizes[a] for a in axes)


def _shard_index(xp: ExecutionPlan, axes: tuple[str, ...]):
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * xp.mesh_sizes[a] + lax.axis_index(a)
    return idx


def _psum(x, axes):
    return lax.psum(x, axes) if axes else x


def _axes_arg(axes: tuple[str, ...]):
    return axes if len(axes) > 1 else axes[0]


@dataclasses.dataclass
class Ctx:
    model: Model
    xp: ExecutionPlan
    pos: Any = None          # decode: (B,) per-row positions (traced)
    q_offset: Any = 0        # prefill/train: global offset of local seq slice
    capture_len: int = 0     # prefill: also emit a decode state of this len
    group: Optional[str] = None  # current layer-group name (policy overrides)

    @property
    def cfg(self):
        return self.model.cfg

    @property
    def geom(self) -> Geometry:
        return self.model.geom

    @property
    def decode(self) -> bool:
        return self.xp.phase == "decode"


# ==========================================================================
# Gather set: which weight subtrees are prefetched per layer, per mode.
# ==========================================================================
def _dep_tp_ok(geom: Geometry, xp: ExecutionPlan, what: str) -> bool:
    """Can DEP run this weight family as TP instead of gathering?"""
    if what == "ffn":
        return geom.ffn_axes == ("model",)
    if what == "attn":
        return (
            geom.attn_tp_ok
            and xp.phase != "decode"
            and geom.model_size > 1
        )
    return False


def moe_split_active(
    geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> bool:
    """Does the DWDP-gather MoE path run the §4.2 split fast path?"""
    pl = geom.moe_placement
    return (
        xp.policy("moe_experts", group).layout == "split"
        and xp.mode == "dwdp"
        and geom.moe_exec == "gather"
        and pl is not None
        and pl.subgroup_size > 1
    )


def dense_split_active(
    geom: Geometry,
    xp: ExecutionPlan,
    axes: tuple[str, ...],
    family: str = "dense_ffn",
    group: Optional[str] = None,
) -> bool:
    """Does a leading-stacked dense family (attn_qkv / attn_out /
    dense_ffn) gathered over ``axes`` use the split-bank representation?

    Split covers the weights-move modes over a single mesh axis (the
    remote-only permutes are single-axis primitives); multi-axis ZeRO-wide
    train gathers and the DEP fallback gathers keep the legacy merged
    landing."""
    return (
        xp.policy(family, group).layout == "split"
        and xp.mode in ("dwdp", "hybrid")
        and len(axes) == 1
        and _axsize(xp, axes) > 1
    )


def split_bank_active(
    geom: Geometry, xp: ExecutionPlan, key: str, group: Optional[str] = None
) -> bool:
    """Unified per-family predicate: does gather_layer emit a SplitBank
    for this gather-set key / attention sub-family? (The one switch the
    roofline/residency accounting mirrors.)"""
    if key == "moe/experts":
        return moe_split_active(geom, xp, group)
    if key in ("attn_qkv", "attn_out"):
        return dense_split_active(geom, xp, geom.attn_axes, key, group)
    if key in ("ffn", "moe/shared"):
        return dense_split_active(geom, xp, geom.ffn_axes, "dense_ffn", group)
    return False


def _routed_tokens(xp: ExecutionPlan) -> int:
    """Per-rank routed token count (static — must agree between
    ``gather_set`` and the ``x2d`` the layer actually routes)."""
    if xp.phase == "decode":
        return max(1, xp.local_batch)
    return max(1, xp.local_batch) * max(1, xp.local_seq)


def demand_fetch_active(
    cfg, geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> bool:
    """Does the MoE gather run the on-demand route-before-gather path?
    (Covers ``fetch="demand"``, ``"predictive"`` and ``"sync_free"`` —
    the predictive engines are refinements of the demand rounds.)

    Requires the split fast path (the demand bank is a split-bank
    refinement) over a single-axis placement, and engages only when
    expected coverage is partial — ``rows * top_k < remote experts`` —
    i.e. when the activated set *can* be a strict subset of the remote
    bank (decode, small-batch prefill). At full coverage the "all"
    gather is never worse, so the plan silently keeps it."""
    fetch = xp.policy("moe_experts", group).fetch
    if fetch not in ("demand", "predictive", "sync_free"):
        return False
    if cfg.moe is None or not moe_split_active(geom, xp, group):
        return False
    if len(geom.expert_axes) != 1:
        return False
    pl = geom.moe_placement
    num_remote = (pl.subgroup_size - 1) * pl.local_count
    return _routed_tokens(xp) * cfg.moe.top_k < num_remote


def predictive_fetch_active(
    cfg, geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> bool:
    """Does the demand path additionally run the predictive engine —
    layer-ahead speculative round + cross-step residency cache +
    post-routing correction round?

    Decode only: the predictor and the cache live in a ``PredictState``
    threaded through the decode-step state, which only the decode loop
    carries. Everywhere else ``fetch="predictive"`` / ``"sync_free"``
    lowers exactly as ``"demand"`` (same rounds, same bitwise
    results)."""
    return (
        xp.phase == "decode"
        and xp.policy("moe_experts", group).fetch
        in ("predictive", "sync_free")
        and demand_fetch_active(cfg, geom, xp, group)
    )


def sync_free_active(
    cfg, geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> bool:
    """Does the predictive decode engine run the SYNC-FREE variant —
    mirrored global ``PredictState`` on every rank, so the speculative
    round's compaction is derived identically on both transfer
    endpoints and ships ZERO index metadata, with all per-layer index
    traffic (residual bitmaps + predictor signals + checksum table)
    packed into the one correction-round all-gather? Implies
    :func:`predictive_fetch_active`; everywhere that predicate is
    false, ``fetch="sync_free"`` lowers exactly as ``"demand"``."""
    return (
        xp.policy("moe_experts", group).fetch == "sync_free"
        and predictive_fetch_active(cfg, geom, xp, group)
    )


def resolve_demand_budget(
    cfg, geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> int:
    """Static per-peer demand-fetch row budget — for predictive-active
    layers this is the *correction* round's budget (the miss-set
    estimate), for plain demand the whole round's.

    A ``moe_experts`` policy ``budget`` > 0 is honored (clamped to the
    per-rank expert count, at which point overflow is impossible). Auto
    (0) applies ``roofline.demand_budget_rows`` — 2x the expected
    per-peer distinct-expert coverage, 8-aligned — or, predictive, the
    correction half of ``roofline.predictive_budget_rows``: the ONE set
    of closed forms the roofline/simulator wire models price, so the
    analytics and the lowered program always ship the same payload.
    Overflow beyond the budget is handled exactly by the per-layer
    fallback, so the estimate only tunes wire bytes, never correctness
    (in particular the correction payload is budget-bounded by the miss
    estimate, never by the expert count)."""
    from repro.core.roofline import demand_budget_rows, predictive_budget_rows

    pl = geom.moe_placement
    assert pl is not None and cfg.moe is not None
    local = pl.local_count
    user = xp.policy("moe_experts", group).budget
    if user > 0:
        return min(user, local)
    draws = _routed_tokens(xp) * cfg.moe.top_k
    if predictive_fetch_active(cfg, geom, xp, group):
        return predictive_budget_rows(draws, cfg.moe.num_experts, local)[1]
    return demand_budget_rows(draws, cfg.moe.num_experts, local)


def resolve_spec_budget(
    cfg, geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> int:
    """Static per-peer row budget of the predictive SPECULATIVE round
    (the layer-ahead prefetch of the predicted hot set). Policy
    ``budget`` > 0 is honored; auto applies the speculative half of
    ``roofline.predictive_budget_rows`` (1x expected coverage). The
    predictor shapes its bitmap to at most this many rows per peer, so
    the speculative round can never overflow — excess predictions are
    simply left to the correction round."""
    from repro.core.roofline import predictive_budget_rows

    pl = geom.moe_placement
    assert pl is not None and cfg.moe is not None
    local = pl.local_count
    user = xp.policy("moe_experts", group).budget
    if user > 0:
        return min(user, local)
    return predictive_budget_rows(
        _routed_tokens(xp) * cfg.moe.top_k, cfg.moe.num_experts, local
    )[0]


def resolve_cache_rows(
    cfg, geom: Geometry, xp: ExecutionPlan, group: Optional[str] = None
) -> int:
    """Rows of the per-layer cross-step expert residency cache: the
    ``moe_experts`` policy's ``cache_budget``, capped at the remote bank
    (caching more than the remote rows buys nothing). 0 = cache off."""
    pl = geom.moe_placement
    assert pl is not None
    remote = (pl.subgroup_size - 1) * pl.local_count
    return min(xp.policy("moe_experts", group).cache_budget, remote)


def fault_stats_active(model: Model, xp: ExecutionPlan) -> bool:
    """Static twin of the validated fetch path's telemetry output: True
    iff this plan's decode step emits ``out["fault_stats"]`` — payload
    validation is on (``xp.validated``: a fault spec to inject, or the
    production ``validate_fetch`` switch) AND at least one MoE layer
    runs the demand/predictive route-before-gather path (the validated
    surface). The vector layout is :data:`faults.FAULT_STAT_BASE` named
    counters followed by per-source-subgroup-position detected counts
    (length ``subgroup_size``), psum'd over all ranks.

    Exception: a sync-free decode layer emits the vector even
    UNVALIDATED — its mirrored-schedule divergence digest always runs
    (it is the mode's consistency contract, not a fault-injection
    feature), so the ``mirror_divergence`` counter must reach the
    HealthMonitor regardless; the other counters are zero then."""
    if model.cfg.moe is None:
        return False
    sync_free = any(
        sig.is_moe and sync_free_active(model.cfg, model.geom, xp, g.name)
        for g in model.plan
        for sig in g.sigs
    )
    if not xp.validated and not sync_free:
        return False
    return sync_free or any(
        sig.is_moe and demand_fetch_active(model.cfg, model.geom, xp, g.name)
        for g in model.plan
        for sig in g.sigs
    )


def _fault_injector(ctx: Ctx, axis: str) -> Optional[faults.FaultInjector]:
    if ctx.xp.fault_spec is None:
        return None
    return faults.FaultInjector(
        ctx.xp.fault_spec, axis, ctx.geom.moe_placement, ctx.xp.mesh_sizes
    )


def _fault_step(ctx: Ctx):
    """Traced decode-step index for fault-key derivation (0 outside
    decode): faults vary per step but are reproducible per step."""
    if ctx.pos is None:
        return jnp.int32(0)
    return jnp.max(ctx.pos).astype(jnp.int32)


def _injected_counts(inj: faults.FaultInjector, key, budget: int, valid):
    """Requester-side recomputation of one payload round's injected-row
    counts ``[drop, zero, corrupt]`` — same key, same masks as the
    tamper site; only rows the plan marked valid count (tampering
    padding rows consumes nothing)."""
    drop, zero, corrupt = inj.payload_masks(key, budget)

    def f(m):
        return jnp.sum((m & valid).astype(jnp.float32))

    return jnp.stack([f(drop), f(zero), f(corrupt)])


def _per_src_detected(bad, budget: int, g: int, p):
    """Attribute each detected payload row to the subgroup position
    that served it (rows are peer-major: chunk t from position
    ``(p + t) % g``)."""
    rows = bad.shape[0]
    if rows == 0:
        return jnp.zeros((g,), jnp.float32)
    src = (p + 1 + jnp.arange(rows, dtype=jnp.int32) // budget) % g
    return jnp.zeros((g,), jnp.float32).at[src].add(bad.astype(jnp.float32))


def gather_set(
    sig: LayerSig,
    geom: Geometry,
    xp: ExecutionPlan,
    cfg=None,
    group: Optional[str] = None,
) -> tuple[tuple[str, ...], ...]:
    """Key paths within a layer param dict that the prefetch pipeline
    gathers before the layer executes.

    Demand-active MoE layers (route-before-gather) exclude the expert
    bank: their gather depends on the current layer's routing, so it
    runs *inside* ``_moe_apply`` instead of the layer-ahead pipeline.
    PREDICTIVE-active layers (decode) re-join the pipeline: their
    speculative round depends only on the cross-step ``PredictState``,
    so it is issued a layer ahead like any other family — that is what
    puts the payload round back under the previous layer's
    attention/compute window — and only the small correction round stays
    inside ``_moe_apply``. ``cfg`` is needed for those eligibility
    checks only; callers that pass none get the demand-oblivious set.
    ``group`` scopes per-layer-group policy overrides."""
    if xp.mode == "replicated":
        return ()
    out: list[tuple[str, ...]] = []
    weights_move = xp.mode in ("dwdp", "hybrid")
    is_attn = sig.kind in (BlockKind.GLOBAL_ATTN, BlockKind.LOCAL_ATTN)
    if is_attn and geom.attn_axes and not _qgather_ok(geom, xp):
        if weights_move or not _dep_tp_ok(geom, xp, "attn"):
            out.append(("attn",))
    if sig.kind == BlockKind.RECURRENT and geom.cell_axes:
        out.append(("rec",))
    if sig.kind in (BlockKind.MLSTM, BlockKind.SLSTM) and geom.cell_axes:
        out.append(("cell",))
    if sig.is_moe:
        pl = geom.moe_placement
        assert pl is not None
        if (
            xp.mode == "dwdp"
            and geom.moe_exec == "gather"
            and pl.subgroup_size > 1
            and not (
                cfg is not None
                and demand_fetch_active(cfg, geom, xp, group)
                and not predictive_fetch_active(cfg, geom, xp, group)
            )
        ):
            out.append(("moe", "experts"))
        if sig.shared_d_ff and geom.ffn_axes:
            if weights_move or not _dep_tp_ok(geom, xp, "ffn"):
                out.append(("moe", "shared"))
    elif sig.ffn_dim and geom.ffn_axes:
        if weights_move or not _dep_tp_ok(geom, xp, "ffn"):
            out.append(("ffn",))
    return tuple(out)


def gathered_wire_bytes_per_step(model: Model, xp: ExecutionPlan) -> dict:
    """Static per-rank gathered-weight wire bytes for one forward step:
    ``{"full": ..., "fetched": ..., "families": {family: {"full": ...,
    "fetched": ...}}}``.

    ``fetched`` is what the lowered program actually ships (demand-active
    expert layers pay the budget-padded payload + the index round);
    ``full`` is the same step under an all-fetch ``moe_experts`` policy —
    the counterfactual the serving metrics report savings against.
    ``families`` breaks both down per gathered-weight family
    (``moe_experts``, ``attn_qkv``, ``attn_out``, ``dense_ffn``) so the
    serving metrics can report per-family traffic, not just the MoE
    total. Predictive/sync-free layers additionally report a ``rounds``
    split — ``{"spec": ..., "corr": ...}`` — separating the layer-ahead
    (overlappable) speculative round from the post-routing
    (critical-path) correction round; plain demand's one post-routing
    round counts under ``corr``, and sync-free steps add a per-STEP
    ``mirror`` entry (the one mirror-fold all-gather, counted once —
    not per layer). Counts the stacked transformer families; the rare
    flat cell/rec gathers are not modeled here.
    """
    cfg, geom = model.cfg, model.geom
    ws = jnp.dtype(model.dtype).itemsize
    d = cfg.d_model
    fams = {
        f: {"full": 0.0, "fetched": 0.0}
        for f in ("moe_experts", "attn_qkv", "attn_out", "dense_ffn")
    }
    rounds = {"spec": 0.0, "corr": 0.0}
    any_rounds = False
    any_sync = False

    def add(fam: str, n_cycles: int, full_b: float, fetched_b=None):
        fams[fam]["full"] += full_b * n_cycles
        fams[fam]["fetched"] += (
            full_b if fetched_b is None else fetched_b
        ) * n_cycles

    for group in model.plan:
        for sig in group.sigs:
            paths = gather_set(sig, geom, xp, cfg, group.name)
            for path in paths:
                key = "/".join(path)
                if key == "moe/experts":
                    pl = geom.moe_placement
                    pe = 3 * d * cfg.moe.d_ff * ws
                    full_b = prefetch.gather_bytes(pl, pe)
                    if predictive_fetch_active(cfg, geom, xp, group.name):
                        # the predictive rounds replace the full gather:
                        # budget-padded speculative round (layer-ahead)
                        # + correction round. Plain predictive pays an
                        # index round on each; sync-free ships a pure-
                        # payload speculative round and packs ALL index
                        # metadata into the correction all-gather.
                        spec_b = resolve_spec_budget(
                            cfg, geom, xp, group.name
                        )
                        corr_b = resolve_demand_budget(
                            cfg, geom, xp, group.name
                        )
                        if sync_free_active(cfg, geom, xp, group.name):
                            any_sync = True
                            by_round = prefetch.sync_free_fetch_bytes(
                                pl, spec_b, corr_b, _routed_tokens(xp),
                                pe, validate=xp.validated,
                            )
                        else:
                            by_round = {
                                "spec": prefetch.demand_fetch_bytes(
                                    pl, spec_b, pe, validate=xp.validated
                                ),
                                "corr": prefetch.demand_fetch_bytes(
                                    pl, corr_b, pe, validate=xp.validated
                                ),
                            }
                        any_rounds = True
                        for rnd in ("spec", "corr"):
                            rounds[rnd] += by_round[rnd] * group.n_cycles
                        fetched = min(
                            full_b, by_round["spec"] + by_round["corr"]
                        )
                        add("moe_experts", group.n_cycles, full_b, fetched)
                    else:
                        add("moe_experts", group.n_cycles, full_b)
                elif key == "attn":
                    a = _axsize(xp, geom.attn_axes)
                    qkv = d * (cfg.q_dim + 2 * cfg.kv_dim) * ws
                    out = cfg.q_dim * d * ws
                    add("attn_qkv", group.n_cycles, qkv * (a - 1) / max(1, a))
                    add("attn_out", group.n_cycles, out * (a - 1) / max(1, a))
                elif key in ("ffn", "moe/shared"):
                    s = _axsize(xp, geom.ffn_axes)
                    f = sig.shared_d_ff if key == "moe/shared" else sig.ffn_dim
                    w = 3 * d * (f or 0) * ws
                    add("dense_ffn", group.n_cycles, w * (s - 1) / max(1, s))
            if (
                sig.is_moe
                and demand_fetch_active(cfg, geom, xp, group.name)
                and not predictive_fetch_active(cfg, geom, xp, group.name)
            ):
                # route-before-gather layers: gather_set excluded the
                # expert bank; the demand fetch happens inside the layer
                pl = geom.moe_placement
                pe = 3 * d * cfg.moe.d_ff * ws
                budget = resolve_demand_budget(cfg, geom, xp, group.name)
                fetched = prefetch.demand_fetch_bytes(
                    pl, budget, pe, validate=xp.validated
                )
                any_rounds = True
                rounds["corr"] += fetched * group.n_cycles
                add("moe_experts", group.n_cycles,
                    prefetch.gather_bytes(pl, pe), fetched)
    if any_sync:
        # the ONE per-step mirror-fold all-gather (routing/position
        # signals) — per STEP, not per layer, so it adds once, outside
        # the group/cycle loops
        mb = float(prefetch.sync_free_mirror_bytes(
            geom.moe_placement, _routed_tokens(xp)
        ))
        rounds["mirror"] = mb
        fams["moe_experts"]["fetched"] += mb
    out = {
        "full": sum(v["full"] for v in fams.values()),
        "fetched": sum(v["fetched"] for v in fams.values()),
        "families": fams,
    }
    if any_rounds:
        out["rounds"] = rounds
    return out


def _extract(lp: dict, paths) -> dict:
    out = {}
    for path in paths:
        sub = lp
        for k in path:
            sub = sub[k]
        out["/".join(path)] = sub
    return out


def _merge(lp: dict, gathered: dict) -> dict:
    if not gathered:
        return lp
    lp = dict(lp)
    for key, sub in gathered.items():
        path = key.split("/")
        node = lp
        for k in path[:-1]:
            node[k] = dict(node[k])
            node = node[k]
        node[path[-1]] = sub
    return lp


def _gather_leading(tree, axes: tuple[str, ...], xp: ExecutionPlan, pol):
    """Legacy merged gather of stacked-storage weights (leading shard
    axis) to full — the *explicit merge step*: every shard, resident
    included, lands once in the canonical contiguous buffer, over the
    family policy's transport. Split mode never calls this for a
    split-active family."""
    size = _axsize(xp, axes)
    if size == 1:
        return tree
    if len(axes) > 1 or pol.transport == "allgather":
        ax = _axes_arg(axes)
        return jax.tree.map(
            lambda w: lax.all_gather(w, ax, axis=0, tiled=True), tree
        )
    pl = make_placement(size, size)
    return prefetch.gather_shards(
        tree, axes[0], pl, mode=pol.transport, num_slices=pol.num_slices
    )


def _leading_placement(axes: tuple[str, ...], xp: ExecutionPlan):
    """Trivial one-slice-per-rank placement for stacked dense families
    (subgroup == the whole axis, local_count == 1)."""
    size = _axsize(xp, axes)
    return make_placement(size, size)


def _gather_flat(tree, axes: tuple[str, ...], xp: ExecutionPlan):
    """Gather flat (last-dim-sharded) cell weights to full.

    Only the 2-D ``w_*`` projection matrices are ZeRO-sharded by the spec
    builder (layer_pspecs); 1-D gains, conv kernels and per-head ``r_*``
    recurrent blocks stay replicated and must pass through untouched.
    """
    if _axsize(xp, axes) == 1:
        return tree
    ax = _axes_arg(axes)
    return {
        k: (
            lax.all_gather(w, ax, axis=w.ndim - 1, tiled=True)
            if (k.startswith("w_") and w.ndim == 2)
            else w
        )
        for k, w in tree.items()
    }


_ATTN_PARTS = (("attn_qkv", ("wq", "wk", "wv")), ("attn_out", ("wo",)))


def _gather_attn(tree: dict, ctx: Ctx):
    """Gather the attention projections as TWO policy families —
    ``attn_qkv`` (wq/wk/wv) and ``attn_out`` (wo) — each under its own
    (layout, transport). Returns a plain merged dict when both parts are
    merged (byte-identical to the legacy whole-family gather) or a
    ``prefetch.AttnBank`` carrying each part's representation when at
    least one is split — which is how a mixed plan runs split QKV next
    to a merged output projection (or vice versa) in one forward."""
    geom, xp = ctx.geom, ctx.xp
    axes = geom.attn_axes
    parts = {}
    for fam, keys in _ATTN_PARTS:
        sub = {k: tree[k] for k in keys}
        pol = xp.policy(fam, ctx.group)
        if dense_split_active(geom, xp, axes, fam, ctx.group):
            parts[fam] = prefetch.gather_split_bank(
                sub, axes[0], _leading_placement(axes, xp),
                mode=pol.transport, num_slices=pol.num_slices,
            )
        else:
            parts[fam] = _gather_leading(sub, axes, xp, pol)
    if not any(isinstance(p, prefetch.SplitBank) for p in parts.values()):
        return {**parts["attn_qkv"], **parts["attn_out"]}
    return prefetch.AttnBank(qkv=parts["attn_qkv"], out=parts["attn_out"])


def _mirror_spec_masks(ctx: Ctx, pred, pl, sbudget: int) -> jax.Array:
    """Sync-free speculative schedule: the ``(G', num_padded)`` predicted
    bitmaps of EVERY subgroup position, derived from the mirrored
    ``PredictState`` alone (global prev/EMA/cache views + the richer
    signals weighted by ``predict_extra_score``). Deterministic in the
    mirror, so the gather site (pipeline, layer-ahead) and the digest
    site (``_moe_demand_apply``, same step, same ``pred``) recompute the
    identical array — that determinism is WHY the speculative round needs
    no index exchange.

    The ``mirror`` fault perturbs the target rank's view of its own row
    here — transiently, at both call sites identically (same pred, same
    step key), never persisted into the state — so the drifted rank
    genuinely derives a different schedule for the digest to catch."""
    geom, xp = ctx.geom, ctx.xp
    prev, ema = pred.prev[0], pred.ema[0]
    cids, cvalid = pred.cache_ids[0], pred.cache_valid[0]
    sig, sigw = pred.sig[0], pred.sigw[0]
    inj = _fault_injector(ctx, geom.expert_axes[0])
    if inj is not None and inj.spec.mirror_rate:
        flag = inj.mirror_flag(_fault_step(ctx))
        p = lax.axis_index(geom.expert_axes[0]) % pl.subgroup_size
        bump = jnp.where(
            jnp.arange(pl.num_padded) % 3 == 0, 10.0, 0.0
        )
        ema = ema.at[p].add(jnp.where(flag, bump, 0.0))
    extra = jax.vmap(prefetch.predict_extra_score)(sig, sigw)

    def one(prev_q, ema_q, ids_q, valid_q, extra_q):
        return prefetch.predict_bitmap(
            prev_q, ema_q, pl, budget=sbudget,
            exclude_ids=ids_q, exclude_valid=valid_q,
            extra_score=extra_q, exclude_peers=xp.exclude_peers,
        )

    return jax.vmap(one)(prev, ema, cids, cvalid, extra)


def _speculative_expert_gather(tree, ctx: Ctx, pred) -> prefetch.DemandBank:
    """The predictive fetch's layer-ahead SPECULATIVE round: a demand
    gather of the predictor's hot set (previous-step routing + EMA, minus
    cache-resident rows), issued from the prefetch pipeline — i.e. during
    the previous layer's attention/compute window, with no dependence on
    this step's routing, so the payload overlaps compute exactly like the
    all-fetch prefetch. The predictor bitmap is shaped to the speculative
    budget per peer, so this round never overflows (misses fall to the
    correction round inside ``_moe_apply``).

    Plain predictive exchanges the bitmaps (``plan_demand_fetch``'s
    all-gather — senders must learn what to serve). SYNC-FREE derives
    every position's bitmap from the mirrored state instead
    (:func:`_mirror_spec_masks`), so this round lowers to payload
    permutes ONLY — zero index metadata on the wire."""
    cfg, geom, xp = ctx.cfg, ctx.geom, ctx.xp
    pl = geom.moe_placement
    axis = geom.expert_axes[0]
    g, local = pl.subgroup_size, pl.local_count
    pol = xp.policy("moe_experts", ctx.group)
    sbudget = resolve_spec_budget(cfg, geom, xp, ctx.group)
    if sync_free_active(cfg, geom, xp, ctx.group):
        sbudget = min(sbudget, local)
        masks = _mirror_spec_masks(ctx, pred, pl, sbudget)
        p = lax.axis_index(axis) % g
        own = lax.dynamic_index_in_dim(masks, p, 0, keepdims=False)
        fetched_ids, valid, _ = prefetch.plan_from_bitmap(
            own, p, g, local, sbudget
        )
        plan = prefetch.DemandPlan(
            masks=masks, fetched_ids=fetched_ids, valid=valid,
            overflow=jnp.bool_(False),
        )
    else:
        wanted = prefetch.predict_bitmap(
            pred.prev[0], pred.ema[0], pl, budget=sbudget,
            exclude_ids=pred.cache_ids[0],
            exclude_valid=pred.cache_valid[0],
            exclude_peers=xp.exclude_peers,
        )
        plan = prefetch.plan_demand_fetch(
            wanted, axis, pl, budget=sbudget, agree_axes=()
        )
    inj = _fault_injector(ctx, axis)
    return prefetch.gather_demand_payload(
        tree, plan, axis, pl, budget=sbudget, mode=pol.transport,
        num_slices=pol.num_slices, injector=inj,
        fault_key=(
            inj.site_key("spec", _fault_step(ctx)) if inj is not None
            else None
        ),
    )


def gather_layer(gsub: dict, ctx: Ctx, pred=None) -> dict:
    """One gather routine for every prefetched family, each under ITS OWN
    policy (``xp.policy(family, group)`` — layout, transport, slicing).

    Split-active families come back as a ``prefetch.SplitBank`` — THE
    canonical gathered representation (remote-only wire traffic, resident
    shard untouched, rotated canonical order); the attention tree splits
    into its qkv/out sub-families (see ``_gather_attn``). A
    predictive-active expert bank (decode) comes back as a compact
    ``prefetch.DemandBank`` instead — the speculative round's fetch of
    the predicted hot set, driven by the layer's ``pred``
    :class:`prefetch.PredictState`. Everything else takes the legacy
    path through the explicit merge (``_gather_leading`` /
    ``gather_shards``), which is the only place a full canonical weight
    buffer is ever created."""
    geom, xp = ctx.geom, ctx.xp
    out = {}
    for key, tree in gsub.items():
        if key in ("rec", "cell"):
            # norms and 1-d params are replicated; only shard-eligible
            # (last dim divisible) leaves were sharded by the spec builder
            out[key] = _gather_flat(tree, geom.cell_axes, xp)
            continue
        if key == "attn":
            out[key] = _gather_attn(tree, ctx)
            continue
        if key in ("ffn", "moe/shared"):
            axes, pl, fam = geom.ffn_axes, None, "dense_ffn"
        elif key == "moe/experts":
            axes, pl, fam = geom.expert_axes, geom.moe_placement, "moe_experts"
            assert pl is not None and len(axes) == 1
            if predictive_fetch_active(ctx.cfg, geom, xp, ctx.group):
                assert pred is not None, (
                    "predictive fetch needs the layer's PredictState in "
                    'the decode state — attach it with '
                    "execution.attach_predict_state(state, model, xp)"
                )
                out[key] = _speculative_expert_gather(tree, ctx, pred)
                continue
        else:
            raise KeyError(key)
        pol = xp.policy(fam, ctx.group)
        if split_bank_active(geom, xp, key, ctx.group):
            out[key] = prefetch.gather_split_bank(
                tree,
                axes[0],
                pl if pl is not None else _leading_placement(axes, xp),
                mode=pol.transport,
                num_slices=pol.num_slices,
            )
        elif pl is not None:
            out[key] = prefetch.gather_shards(
                tree, axes[0], pl, mode=pol.transport,
                num_slices=pol.num_slices,
            )
        else:
            out[key] = _gather_leading(tree, axes, xp, pol)
    return out


# ==========================================================================
# Embedding / head.
# ==========================================================================
def _embed_table(params, ctx: Ctx):
    """Full (V_pad, D) embedding — gathered over the vocab shards."""
    emb = params["embed"]
    if ctx.geom.model_size > 1:
        emb = lax.all_gather(emb, AXIS_MODEL, axis=0, tiled=True)
    return emb


def _compute_dtype(model):
    if model.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        return jnp.bfloat16
    return model.dtype


def _embed_decode(params, token, ctx: Ctx):
    emb = params["embed"]  # local (V_l, D)
    v_l = emb.shape[0]
    off = lax.axis_index(AXIS_MODEL) * v_l if ctx.geom.model_size > 1 else 0
    idx = token - off
    valid = (idx >= 0) & (idx < v_l)
    cd = _compute_dtype(ctx.model)
    x = emb[jnp.clip(idx, 0, v_l - 1)].astype(cd) * valid[..., None].astype(cd)
    if ctx.geom.model_size > 1:
        x = lax.psum(x, AXIS_MODEL)
    return x


def _head_local(params, ctx: Ctx):
    """Local (D, V_l) head slice for decode/prefill logits."""
    if ctx.cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def _mask_vocab_cols(logits, ctx: Ctx, local: bool):
    v = ctx.cfg.vocab_size
    v_tot = ctx.geom.vocab_pad
    if v == v_tot:
        return logits
    n = logits.shape[-1]
    if local and ctx.geom.model_size > 1:
        off = lax.axis_index(AXIS_MODEL) * n
    else:
        off = 0
    cols = off + jnp.arange(n)
    return jnp.where(cols < v, logits, -1e30)


# ==========================================================================
# Attention.
# ==========================================================================
def _w(w, like):
    """Dequantize-on-use: fp8-stored weights compute in the activation
    dtype (the paper's NVFP4-storage analogue)."""
    return w.astype(like.dtype) if w.dtype != like.dtype else w


def _project_heads(h, w, heads, head_dim):
    """h: (B,S,D); w: (A, D, dim/A) stacked -> (B,S,heads,head_dim)."""
    b, s, _ = h.shape
    out = jnp.einsum("bsd,adh->bsah", h, _w(w, h))
    return out.reshape(b, s, heads, head_dim)


def _dedupe_kv(w, geom: Geometry):
    """Gathered kv weights (A, D, kvd/ks) -> (ks, D, kvd/ks)."""
    a = w.shape[0]
    if a > geom.kv_shard:
        w = w[:: a // geom.kv_shard]
    return w


def _attn_split_position(geom: Geometry):
    """Caller position on the (single-axis) attention shard ring."""
    return lax.axis_index(geom.attn_axes[0]) % geom.attn_shards


def _attn_split_qkv(h, bank, ctx: Ctx):
    """q/k/v projections straight off a SplitBank — no merged ``(A, D,
    qd/A)`` weight stack ever exists.

    The split kernel emits per-slice outputs in rotated bank order
    (resident slice first); the roll back to canonical head order happens
    on the *projected activations* (a gather of (T, A, fs) — a factor
    D/fs smaller than the weight merge the paper eliminates, and pure
    index arithmetic on the weight side). KV slices are computed for all
    A stacked positions and deduped post-projection — a GQA-duplicate
    recompute bounded by A/kv_shard on the (small) KV projections.
    """
    cfg, geom = ctx.cfg, ctx.geom
    a = geom.attn_shards
    p = _attn_split_position(geom)
    b, s, dm = h.shape
    h2d = h.reshape(b * s, dm)
    impl = split_gemm_lib.default_dense_impl(ctx.xp.phase)
    canon = (jnp.arange(a) - p) % a  # canonical slice j sits at rotated j-p

    def stack(name):
        out = split_gemm_lib.split_stack_matmul(
            h2d, bank.local[name], bank.remote[name], impl=impl
        )  # (A, T, fs) rotated
        return jnp.take(jnp.moveaxis(out, 0, 1), canon, axis=1)  # (T, A, fs)

    hd = cfg.head_dim
    q = stack("wq").reshape(b, s, cfg.num_heads, hd)
    dup = a // geom.kv_shard
    k = stack("wk")[:, ::dup].reshape(b, s, cfg.num_kv_heads, hd)
    v = stack("wv")[:, ::dup].reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


def _attn_split_out(out, bank, ctx: Ctx):
    """Output projection off a SplitBank: roll the attention output's
    head slices into rotated bank order (activation-side, index-only),
    then let the reduce kernel sum per-slice contributions — the sum is
    order-independent, so no post-fix-up is needed."""
    geom = ctx.geom
    a = geom.attn_shards
    p = _attn_split_position(geom)
    b, s = out.shape[:2]
    impl = split_gemm_lib.default_dense_impl(ctx.xp.phase)
    rot = (jnp.arange(a) + p) % a  # rotated slice j is canonical p+j
    out = jnp.take(out.reshape(b, s, a, -1), rot, axis=2)
    out = jnp.moveaxis(out.reshape(b * s, a, -1), 1, 0)  # (A, T, fs)
    y = split_gemm_lib.split_reduce_matmul(
        out, bank.local["wo"], bank.remote["wo"], impl=impl
    )
    return y.reshape(b, s, -1)


def _attn_full(h, aw, sig: LayerSig, ctx: Ctx, lstate):
    """Full-weight attention: replicated, DWDP-gathered merged, the §4.2
    split fast path, or any per-family mix of the two.

    ``aw`` is a flat weight dict (replicated / fully merged), a whole
    ``prefetch.SplitBank`` (both attention families split), or a
    ``prefetch.AttnBank`` whose qkv/out parts carry each family's own
    representation — so ``attn_qkv`` and ``attn_out`` policies compose
    freely (split QKV feeding a merged output projection and vice
    versa). The split QKV path rolls its outputs back to canonical head
    order, which is exactly the order the merged out path consumes."""
    cfg, geom, xp = ctx.cfg, ctx.geom, ctx.xp
    b, s, _ = h.shape
    hd = cfg.head_dim
    if isinstance(aw, prefetch.AttnBank):
        qkv_w, out_w = aw.qkv, aw.out
    else:
        qkv_w = out_w = aw
    if isinstance(qkv_w, prefetch.SplitBank):
        q, k, v = _attn_split_qkv(h, qkv_w, ctx)
    else:
        q = _project_heads(h, qkv_w["wq"], cfg.num_heads, hd)
        wk = _dedupe_kv(qkv_w["wk"], geom)
        wv = _dedupe_kv(qkv_w["wv"], geom)
        k = _project_heads(h, wk, cfg.num_kv_heads, hd)
        v = _project_heads(h, wv, cfg.num_kv_heads, hd)

    if ctx.decode:
        pos = ctx.pos  # (B,) per-row decode positions
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        out, new_state = _attn_decode_cache(q, k, v, sig, ctx, lstate)
    else:
        positions = ctx.q_offset + jnp.arange(s)
        posb = jnp.broadcast_to(positions, (b, s))
        q = apply_rope(q, posb, cfg.rope_theta)
        k = apply_rope(k, posb, cfg.rope_theta)
        if xp.seq_axes:
            ax = _axes_arg(xp.seq_axes)
            k = lax.all_gather(k, ax, axis=1, tiled=True)
            v = lax.all_gather(v, ax, axis=1, tiled=True)
        out = attn_lib.mha_prefill(
            q, k, v, window=sig.window, q_offset=ctx.q_offset,
            block_causal=ctx.xp.block_causal,
        )
        if ctx.capture_len:
            new_state = _capture_kv_state(k, v, sig, ctx)
        else:
            new_state = lstate
    if isinstance(out_w, prefetch.SplitBank):
        return _attn_split_out(out, out_w, ctx), new_state
    a = out_w["wo"].shape[0]
    out = out.reshape(b, out.shape[1], a, -1)
    y = jnp.einsum("bsag,agd->bsd", out, _w(out_w["wo"], out))
    return y, new_state


def _attn_decode_cache(q, k_new, v_new, sig: LayerSig, ctx: Ctx, lstate):
    """Write each row's new token into the (possibly seq-sharded, possibly
    ring) KV cache, then partial-attend + psum-LSE combine across shards.

    Positions are per-row (B,) so continuously-batched rows can sit at
    different depths; the write is a one-hot masked select per row."""
    xp = ctx.xp
    pos = ctx.pos  # (B,)
    l_local = lstate["k"].shape[1]
    n_sh = xp.seq_shards if xp.seq_axes else 1
    l_total = l_local * n_sh
    slot = pos % l_total                      # (B,)
    owner = slot // l_local
    li = slot % l_local
    mine = _shard_index(xp, xp.seq_axes) if xp.seq_axes else jnp.int32(0)

    write = (owner == mine)                   # (B,)
    onehot = (
        jnp.arange(l_local)[None, :] == li[:, None]
    ) & write[:, None]                        # (B, L_local)
    ck = jnp.where(
        onehot[:, :, None, None],
        k_new.astype(lstate["k"].dtype),      # (B,1,Kh,hd) broadcasts over L
        lstate["k"],
    )
    cv = jnp.where(
        onehot[:, :, None, None],
        v_new.astype(lstate["v"].dtype),
        lstate["v"],
    )
    sp = jnp.where(onehot, pos[:, None], lstate["slot_pos"])
    new_state = {"k": ck, "v": cv, "slot_pos": sp}

    out, lse = attn_lib.mha_decode_partial(
        q[:, 0],
        ck.astype(q.dtype),
        cv.astype(q.dtype),
        sp,
        pos,
        window=sig.window,
    )
    if xp.seq_axes:
        m = lax.pmax(lse, xp.seq_axes)
        w = jnp.exp(lse - m)
        num = lax.psum(out.astype(jnp.float32) * w[..., None], xp.seq_axes)
        den = lax.psum(w, xp.seq_axes)
        out = (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
    return out[:, None], new_state  # (B,1,H,hd)


def _capture_kv_state(k, v, sig: LayerSig, ctx: Ctx):
    """Turn prefill K/V into a ring-buffer decode state (the disaggregated
    ctx->gen KV transfer payload). Ring slot l holds the latest position
    p < S with p % L == l; slots that never filled stay empty (-1).

    Works under SEQUENCE SHARDING too: the prefill attention path
    all-gathers K/V over the seq axes before attending (``_attn_full``),
    so ``k``/``v`` here always carry the full global sequence — each rank
    simply keeps the ring slots it owns under the decode cache layout
    (``slot // l_local == mine``, matching ``_attn_decode_cache``)."""
    xp = ctx.xp
    b, s = k.shape[0], k.shape[1]
    length = min(sig.window, ctx.capture_len) if sig.window else ctx.capture_len
    n_sh = xp.seq_shards if xp.seq_axes else 1
    assert length % n_sh == 0, (
        f"KV capture ring length {length} "
        f"({'window-limited, window=' + str(sig.window) if sig.window and sig.window < ctx.capture_len else 'capture_len'}) "
        f"must divide over the {n_sh} sequence shards — pick a "
        "cache_len (and, for local-attention layers, a window) divisible "
        "by the seq-shard count, or prefill on an unsharded-sequence mesh"
    )
    l_local = length // n_sh
    mine = _shard_index(xp, xp.seq_axes) if xp.seq_axes else jnp.int32(0)
    l_idx = mine * l_local + jnp.arange(l_local)  # global slots owned here
    pos_l = (s - 1) - ((s - 1 - l_idx) % length)
    valid = pos_l >= 0
    take = jnp.clip(pos_l, 0, s - 1)
    ck = jnp.take(k, take, axis=1) * valid[None, :, None, None].astype(k.dtype)
    cv = jnp.take(v, take, axis=1) * valid[None, :, None, None].astype(v.dtype)
    slot_pos = jnp.broadcast_to(
        jnp.where(valid, pos_l, -1)[None, :], (b, l_local)
    ).astype(jnp.int32)
    return {"k": ck, "v": cv, "slot_pos": slot_pos}


def _qgather_ok(geom: Geometry, xp: ExecutionPlan) -> bool:
    return (
        xp.phase == "decode"
        and getattr(xp, "decode_attn", "gather") == "qgather"
        and geom.attn_axes == ("model",)
        and AXIS_MODEL not in xp.batch_axes
        and geom.model_size > 1
    )


def _attn_decode_qgather(h, aw, sig: LayerSig, ctx: Ctx, lstate):
    """Beyond-paper decode attention for sharded attention weights: keep
    weights LOCAL and all-gather the projected q/k/v activations instead
    (B x 1 x dim — a few hundred KB vs hundreds of MB of weights/layer).
    Requires tokens replicated over "model" (decode with seq-sharded KV).
    """
    cfg, geom, xp = ctx.cfg, ctx.geom, ctx.xp
    b = h.shape[0]
    hd = cfg.head_dim
    g = geom.attn_shards
    ks = geom.kv_shard
    # local feature slices: (B, 1, qd/g) and (B, 1, kvd/ks)
    q_l = jnp.einsum("bsd,adh->bsh", h, _w(aw["wq"], h))
    k_l = jnp.einsum("bsd,adh->bsh", h, _w(aw["wk"], h))
    v_l = jnp.einsum("bsd,adh->bsh", h, _w(aw["wv"], h))
    q = lax.all_gather(q_l, AXIS_MODEL, axis=2, tiled=True)  # (B,1,qd)
    kg = lax.all_gather(k_l, AXIS_MODEL, axis=2, tiled=True)
    vg = lax.all_gather(v_l, AXIS_MODEL, axis=2, tiled=True)
    q = q.reshape(b, 1, cfg.num_heads, hd)
    # kv gathered rank-major contains g/ks duplicates per group: dedupe
    dup = g // ks
    kvd_l = cfg.kv_dim // ks
    k = kg.reshape(b, 1, g, kvd_l)[:, :, ::dup].reshape(
        b, 1, cfg.num_kv_heads, hd
    )
    v = vg.reshape(b, 1, g, kvd_l)[:, :, ::dup].reshape(
        b, 1, cfg.num_kv_heads, hd
    )
    pos = ctx.pos
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    out, new_state = _attn_decode_cache(q, k, v, sig, ctx, lstate)
    # out (B,1,H,hd) replicated over "model" (LSE combine psums it);
    # slice my flat-q features and apply the local wo shard + psum
    qd_l = cfg.q_dim // g
    flat = out.reshape(b, 1, cfg.q_dim)
    my = lax.dynamic_slice_in_dim(
        flat, lax.axis_index(AXIS_MODEL) * qd_l, qd_l, axis=2
    )
    y = jnp.einsum("bsg,agd->bsd", my, _w(aw["wo"], my))
    return lax.psum(y, AXIS_MODEL), new_state


def _attn_tp(h, aw, sig: LayerSig, ctx: Ctx):
    """DEP tensor-parallel attention: gather tokens over "model", compute
    the local head slice, reduce-scatter back — the synchronizing
    activation collectives DWDP removes."""
    cfg, xp = ctx.cfg, ctx.xp
    hd = cfg.head_dim
    g = ctx.geom.attn_shards
    token_axis = 0 if AXIS_MODEL in xp.batch_axes else 1
    hg = lax.all_gather(h, AXIS_MODEL, axis=token_axis, tiled=True)
    b, s, _ = hg.shape
    heads_l = cfg.num_heads // g
    q = _project_heads(hg, aw["wq"], heads_l, hd)
    kv_l = cfg.num_kv_heads // ctx.geom.kv_shard
    k = _project_heads(hg, aw["wk"], kv_l, hd)
    v = _project_heads(hg, aw["wv"], kv_l, hd)
    if token_axis == 1:
        positions = jnp.arange(s)
    else:
        positions = ctx.q_offset + jnp.arange(s)
    posb = jnp.broadcast_to(positions, (b, s))
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    out = attn_lib.mha_prefill(q, k, v, window=sig.window)
    out = out.reshape(b, s, 1, heads_l * hd)
    y = jnp.einsum("bsag,agd->bsd", out, aw["wo"])
    return lax.psum_scatter(
        y, AXIS_MODEL, scatter_dimension=token_axis, tiled=True
    )


# ==========================================================================
# FFN (dense "virtual experts") + MoE.
# ==========================================================================
def _ffn_full(x2d, fp):
    """x2d: (T,D); fp stacked (S,D,F/S) full content."""
    h = jax.nn.silu(
        jnp.einsum("td,sdf->tsf", x2d, _w(fp["w_gate"], x2d))
    ) * jnp.einsum("td,sdf->tsf", x2d, _w(fp["w_up"], x2d))
    return jnp.einsum("tsf,sfd->td", h, _w(fp["w_down"], x2d))


def _ffn_apply(x2d, fp, ctx: Ctx, gathered=None):
    geom, xp = ctx.geom, ctx.xp
    if not geom.ffn_axes:
        return _ffn_full(x2d, fp)
    if xp.mode in ("dwdp", "hybrid") or not _dep_tp_ok(geom, xp, "ffn"):
        assert gathered is not None, "DWDP FFN weights must be prefetched"
        if isinstance(gathered, prefetch.SplitBank):
            # split layout: y = sum_s swiglu_s(x) over (resident, remote)
            # slice banks — the stacked-FFN sum is order-independent, so
            # the rotated bank order needs no fix-up and no merged
            # (S, D, F/S) buffer ever exists.
            lo, re = gathered.local, gathered.remote
            return split_gemm_lib.split_dense_ffn(
                x2d,
                lo["w_gate"], lo["w_up"], lo["w_down"],
                re["w_gate"], re["w_up"], re["w_down"],
                impl=split_gemm_lib.default_dense_impl(xp.phase),
            )
        return _ffn_full(x2d, gathered)
    # DEP TP over "model"
    if ctx.decode:
        # tokens replicated over "model": partial-F compute + psum
        h = jax.nn.silu(x2d @ _w(fp["w_gate"][0], x2d)) * (
            x2d @ _w(fp["w_up"][0], x2d)
        )
        y = h @ _w(fp["w_down"][0], x2d)
        return lax.psum(y, AXIS_MODEL)
    # sequence-parallel TP: gather tokens, compute local F slice, scatter
    xg = lax.all_gather(x2d, AXIS_MODEL, axis=0, tiled=True)
    h = jax.nn.silu(xg @ _w(fp["w_gate"][0], xg)) * (xg @ _w(fp["w_up"][0], xg))
    y = h @ _w(fp["w_down"][0], xg)
    return lax.psum_scatter(y, AXIS_MODEL, scatter_dimension=0, tiled=True)


def _expert_collective(geom: Geometry, xp: ExecutionPlan):
    """(axis_arg, axis_index_groups) for DEP all-to-all within subgroups."""
    pl = geom.moe_placement
    assert pl is not None
    axes = geom.expert_axes
    if pl.redundancy == 1:
        return _axes_arg(axes), None
    ms = xp.mesh_sizes[axes[-1]]
    g = pl.subgroup_size
    if g <= ms and ms % g == 0:
        groups = [
            [j * g + i for i in range(g)] for j in range(ms // g)
        ]
        return axes[-1], groups
    return _axes_arg(axes), pl.axis_index_groups()


def _rotate_moe(xe, experts, ctx: Ctx):
    """Ring-rotate expert shards through ranks, computing each resident
    shard's contribution. Memory: 2x the local shard instead of the full
    layer (the TPU adaptation of on-demand expert fetch; DESIGN.md §2)."""
    geom, xp = ctx.geom, ctx.xp
    pl = geom.moe_placement
    assert pl is not None
    g = pl.subgroup_size
    local = pl.local_count
    ye0 = jnp.zeros(xe.shape, xe.dtype)
    if g == 1:
        return _grouped_into(xe, ye0, experts, jnp.int32(0), local)
    axes = geom.expert_axes
    ms = xp.mesh_sizes[axes[-1]]

    if g <= ms:
        ax = axes[-1]
        p = lax.axis_index(ax) % g
        pairs = [
            (int(b0 + i), int(b0 + (i + 1) % g))
            for b0 in range(0, ms, g)
            for i in range(g)
        ]

        def body(carry, t):
            cur, ye = carry
            src = (p - t) % g
            ye = _grouped_into(xe, ye, cur, src * local, local)
            cur = jax.tree.map(lambda w: lax.ppermute(w, ax, pairs), cur)
            return (cur, ye), None

        # g-1 permuted steps + one final compute without the realignment
        # permute: total traffic (g-1)/g of the layer set, so redundant
        # placement (smaller g) genuinely reduces wire bytes (paper §2).
        (cur, ye), _ = lax.scan(body, (experts, ye0), jnp.arange(g - 1))
        src_last = (p - (g - 1)) % g
        ye = _grouped_into(xe, ye, cur, src_last * local, local)
        return ye

    # nested: inner ring over "model", outer ring over "data" rows
    assert g % ms == 0 and len(axes) == 2
    dp = g // ms
    d_ax, m_ax = axes
    d_size = xp.mesh_sizes[d_ax]
    dc = lax.axis_index(d_ax) % dp
    m = lax.axis_index(m_ax)
    inner_pairs = [(i, (i + 1) % ms) for i in range(ms)]
    outer_pairs = [
        (int(b0 + i), int(b0 + (i + 1) % dp))
        for b0 in range(0, d_size, dp)
        for i in range(dp)
    ]

    def outer(carry, o):
        cur, ye = carry

        def inner(c2, i):
            cur2, ye2 = c2
            src = ((dc - o) % dp) * ms + ((m - i) % ms)
            ye2 = _grouped_into(xe, ye2, cur2, src * local, local)
            cur2 = jax.tree.map(
                lambda w: lax.ppermute(w, m_ax, inner_pairs), cur2
            )
            return (cur2, ye2), None

        (cur, ye), _ = lax.scan(inner, (cur, ye), jnp.arange(ms))
        cur = jax.tree.map(lambda w: lax.ppermute(w, d_ax, outer_pairs), cur)
        return (cur, ye), None

    (_, ye), _ = lax.scan(outer, (experts, ye0), jnp.arange(dp))
    return ye


def _grouped_into(xe, ye, experts, start, count):
    xe_t = lax.dynamic_slice_in_dim(xe, start, count, axis=0)
    ye_t = moe_lib.grouped_ffn(
        xe_t, experts["w_gate"], experts["w_up"], experts["w_down"]
    )
    return lax.dynamic_update_slice_in_dim(ye, ye_t, start, axis=0)


def _rolled_dispatch(d, roll, e_pad: int, capacity: int):
    """Rotate the dispatch's expert coordinate by ``-roll`` (mod e_pad) so
    the caller's resident experts occupy positions [0, local) — the order
    the split banks arrive in (prefetch.gather_remote_shards). Only
    ``flat_slot`` moves; gates / combine weights are order-independent."""
    exp = d.flat_slot // capacity
    slot = d.flat_slot - exp * capacity
    exp = (exp - roll) % e_pad
    return d._replace(flat_slot=exp * capacity + slot)


def _moe_demand_apply(x2d, experts, d, cap: int, ctx: Ctx,
                      spec_bank=None, pred=None):
    """Route-before-gather MoE execution (``fetch="demand"`` and the
    ``fetch="predictive"`` decode engine).

    The routing decision ``d`` already exists — this is the inverted
    layer order — so the activated-expert bitmap is exact, not a
    prediction. Round 1 (index exchange) always runs: it is a few
    hundred bytes and produces the axis-agreed overflow flag that picks
    the branch. Only the taken branch's payload permutes execute:

    - demand: fetch the activated remote experts compacted to the
      per-peer budget, remap the dispatch's expert coordinate through
      ``fetched_ids`` (resident experts at [0, local) in storage order,
      fetched rows after them — index arithmetic only, the demand
      analogue of the PR 1 rotation roll), and run the
      validity-predicated demand kernel over the compact
      ``(local + fetched)`` bank. No buffer wider than that exists.
    - overflow fallback: the PR 1 split path verbatim (full remote bank,
      rolled dispatch) — exact for any routing, so correctness never
      depends on the budget estimate.

    Predictive decode (``spec_bank``/``pred`` given) refines the demand
    round into a latency engine: the wanted set is first served from the
    cross-step residency cache (``pred.cache*`` — rows fetched on
    earlier steps, bit-identical to re-fetching) and the layer-ahead
    SPECULATIVE round's bank (``spec_bank``, fetched under the previous
    layer's compute window); only the miss set rides the post-routing
    CORRECTION round (``plan_demand_fetch(exclude_ids=...)`` — the same
    bitmap/ascending-id contract over the already-subtracted bitmap).
    The kernel consumes the concatenated (cache | speculative |
    correction) rows as one fetched bank through the same ``fetched_ids``
    remap, so the compute is bitwise-identical to the plain demand and
    all-fetch paths for ANY predictor quality and ANY cache budget; a
    correction overflow falls back to the full gather exactly as demand
    does. The predictor (prev bitmap + EMA) updates branch-independently;
    the cache inserts this step's fetched rows, evicting by EMA hotness.
    """
    cfg, geom, xp = ctx.cfg, ctx.geom, ctx.xp
    pl = geom.moe_placement
    assert pl is not None
    axis = geom.expert_axes[0]
    g, local = pl.subgroup_size, pl.local_count
    e_pad = pl.num_padded
    t = x2d.shape[0]
    pol = xp.policy("moe_experts", ctx.group)
    budget = resolve_demand_budget(cfg, geom, xp, ctx.group)
    p = lax.axis_index(axis) % g
    predictive = pred is not None
    # pallas_call has no VJP; the jnp formulation (still merge-free)
    # carries the ZeRO-style train gathers
    impl = "jnp" if xp.phase == "train" else "pallas"

    # payload validation (fault tolerance): when the plan validates,
    # the source-rank checksum table rides the (tiny) metadata round and
    # every arrived/cached row is re-checksummed — mismatches are masked
    # invalid so they flow into the correction round / axis-agreed
    # full-gather fallback, keeping outputs bitwise-exact under faults.
    all_axes = tuple(xp.mesh_sizes)
    validate = xp.validated
    inj = _fault_injector(ctx, axis)
    table = prefetch.checksum_table(experts, axis, pl) if validate else None
    step_idx = _fault_step(ctx) if validate else None
    n_ranks = math.prod(xp.mesh_sizes.values())

    # activated-expert bitmap from the routing decision. Kept tokens
    # only: dropped tokens carry zero combine weight and dispatch zeroed
    # rows, so their experts need no fetch.
    wanted = (
        jnp.zeros((e_pad,), bool).at[d.top_experts.reshape(-1)].max(d.keep)
    )
    if predictive:
        assert spec_bank is not None
        sync_free = sync_free_active(cfg, geom, xp, ctx.group)
        sbudget = min(resolve_spec_budget(cfg, geom, xp, ctx.group), local)
        cbudget = min(budget, local)
        if sync_free:
            # mirrored views: leading dim = subgroup position. This
            # rank's own slots are the position-p rows.
            m_ema = pred.ema[0]
            m_cids, m_cvalid = pred.cache_ids[0], pred.cache_valid[0]
            cache_ids = lax.dynamic_index_in_dim(
                m_cids, p, 0, keepdims=False
            )
            cache_valid = lax.dynamic_index_in_dim(
                m_cvalid, p, 0, keepdims=False
            )
        else:
            ema = pred.ema[0]
            cache_ids, cache_valid = pred.cache_ids[0], pred.cache_valid[0]
        cache_w = jax.tree.map(lambda w: w[0], pred.cache)
        n_cache = cache_ids.shape[0]
        cache_tamper = jnp.zeros((n_cache,), bool)
        if inj is not None and n_cache:
            # residency-cache corruption: rows rot in place between steps
            cache_tamper = inj.cache_mask(
                inj.site_key("cache", step_idx), n_cache
            )
            cache_w = inj.tamper_rows(
                cache_w, jnp.zeros((n_cache,), bool), cache_tamper
            )
        if sync_free:
            # mirrored-schedule divergence cross-check: every rank
            # re-derives the speculative schedule the pipeline gather
            # used (same pred, same step => identical array) and psums a
            # scalar digest over the subgroup. Any mismatch means some
            # rank's mirror drifted — its speculative payload rows are
            # mislabeled — so the spec bank is discarded everywhere and
            # the step takes the exact full-gather fallback. The digest
            # runs UNCONDITIONALLY (it is the mode's consistency
            # contract), validation on or off.
            masks = _mirror_spec_masks(ctx, pred, pl, sbudget)
            dg = prefetch.schedule_digest(masks)
            tot = lax.psum(
                dg, axis, axis_index_groups=pl.axis_index_groups()
            )
            div_local = jnp.abs(g * dg - tot) > 0.5
            diverged_g = (
                lax.psum(div_local.astype(jnp.float32), all_axes) > 0
            )
        if validate:
            # verify cached + speculative rows BEFORE the exclusion set
            # is built: faulty rows fall out of "have", so the
            # correction round re-fetches them — the in-band repair.
            cache_valid_v, bad_cache = prefetch.verify_rows(
                cache_w, cache_ids, cache_valid, table
            )
            spec_valid_v, bad_spec = prefetch.verify_rows(
                spec_bank.fetched, spec_bank.fetched_ids, spec_bank.valid,
                table,
            )
        else:
            cache_valid_v, spec_valid_v = cache_valid, spec_bank.valid
        # under divergence the spec rows are untrusted on every rank
        # (branch-uniform: diverged_g is psum-agreed)
        spec_valid_eff = (
            spec_valid_v & ~diverged_g if sync_free else spec_valid_v
        )
        have_ids = jnp.concatenate([cache_ids, spec_bank.fetched_ids])
        have_valid = jnp.concatenate([cache_valid_v, spec_valid_eff])
        if sync_free:
            # correction round, sync-free form: the residual (miss)
            # bitmap all-gather is the mode's ONLY per-layer index
            # traffic — the senders need every requester's residual to
            # compact the payload, exactly the demand contract. The
            # routing/position signals that feed the mirrors are
            # returned to ``forward_decode`` instead (PredictState.
            # routed), which unions them across layers and runs ONE
            # per-step mirror fold after the stack.
            residual = wanted & ~prefetch.exclude_bitmap(
                e_pad, have_ids, have_valid
            )
            k_top = d.top_experts.shape[-1]
            routed = prefetch.routed_bitmaps(
                jnp.where(
                    d.keep.reshape(-1, k_top), d.top_experts, e_pad
                ),
                e_pad,
            )
            resid_all = lax.all_gather(
                residual, axis, axis_index_groups=pl.axis_index_groups()
            )
            corr_ids, corr_valid, ovf_raw = prefetch.plan_from_bitmap(
                residual, p, g, local, cbudget
            )
            overflow = (
                lax.psum(ovf_raw.astype(jnp.float32), all_axes) > 0
            )
            plan = prefetch.DemandPlan(
                masks=resid_all, fetched_ids=corr_ids, valid=corr_valid,
                overflow=overflow,
            )
        else:
            plan = prefetch.plan_demand_fetch(
                wanted, axis, pl, budget=budget,
                agree_axes=tuple(xp.mesh_sizes),
                exclude_ids=have_ids, exclude_valid=have_valid,
            )
            # predictor update — pure index arithmetic, branch-independent
            new_prev = wanted
            new_ema = (
                prefetch.EMA_DECAY * ema
                + (1.0 - prefetch.EMA_DECAY) * wanted.astype(jnp.float32)
            )
        # hit/miss accounting (rows of the wanted REMOTE set), split by
        # serving tier: residency cache first, speculative round for the
        # rest — the tiers are id-disjoint by the exclusion chain, the
        # bitmap intersection just makes the split robust to overlap
        local_mask = jnp.zeros((e_pad,), bool).at[
            p * local + jnp.arange(local)
        ].set(True)
        wanted_remote = wanted & ~local_mask
        spec_map = prefetch.exclude_bitmap(
            e_pad, spec_bank.fetched_ids, spec_valid_eff
        )
        cache_map = prefetch.exclude_bitmap(e_pad, cache_ids, cache_valid_v)
        n_want = jnp.sum(wanted_remote).astype(jnp.float32)
        n_cache_hit = jnp.sum(wanted_remote & cache_map).astype(jnp.float32)
        n_spec = jnp.sum(
            wanted_remote & spec_map & ~cache_map
        ).astype(jnp.float32)
        n_pred = jnp.sum(spec_bank.valid).astype(jnp.float32)
    else:
        plan = prefetch.plan_demand_fetch(
            wanted, axis, pl, budget=budget, agree_axes=tuple(xp.mesh_sizes)
        )

    def _remap_and_run(d, fetched, ids, valid):
        # expert-id -> compact-bank position. Experts neither resident
        # nor fetched receive only zero-weight traffic (every kept
        # token's expert is in the bitmap), so they may map anywhere
        # in range; position 0 keeps the scatter dense.
        rows = valid.shape[0]
        pos = jnp.zeros((e_pad,), jnp.int32)
        pos = pos.at[p * local + jnp.arange(local)].set(
            jnp.arange(local, dtype=jnp.int32)
        )
        pos = pos.at[jnp.where(valid, ids, e_pad)].set(
            local + jnp.arange(rows, dtype=jnp.int32), mode="drop"
        )
        exp = d.flat_slot // cap
        slot = d.flat_slot - exp * cap
        d2 = d._replace(flat_slot=pos[exp] * cap + slot)
        xe = moe_lib.dispatch_tokens(x2d, d2, local + rows, cap)
        ye = split_gemm_lib.split_swiglu_demand(
            xe,
            experts["w_gate"], experts["w_up"], experts["w_down"],
            fetched["w_gate"], fetched["w_up"], fetched["w_down"],
            valid,
            impl=impl,
        )
        return moe_lib.combine_tokens(ye, d2, t)

    def full_path(experts, d):
        lo, re = prefetch.gather_remote_shards(
            experts, axis, pl, mode=pol.transport, num_slices=pol.num_slices
        )
        d2 = _rolled_dispatch(d, p * local, e_pad, cap)
        xe = moe_lib.dispatch_tokens(x2d, d2, e_pad, cap)
        ye = split_gemm_lib.split_swiglu(
            xe,
            lo["w_gate"], lo["w_up"], lo["w_down"],
            re["w_gate"], re["w_up"], re["w_down"],
            impl=impl,
        )
        return moe_lib.combine_tokens(ye, d2, t)

    if not predictive:
        if not validate:
            # plain demand: both branches of the cond carry their own
            # payload collectives — only the taken branch's permutes
            # execute.
            def demand_branch(experts, d):
                bank = prefetch.gather_demand_payload(
                    experts, plan, axis, pl, budget=budget,
                    mode=pol.transport, num_slices=pol.num_slices,
                )
                return _remap_and_run(
                    d, bank.fetched, plan.fetched_ids, plan.valid
                )

            y = lax.cond(
                plan.overflow, full_path, demand_branch, experts, d
            )
            return y, None, None
        # validated demand: the payload round + compact kernel run
        # UNCONDITIONALLY and the cond only swaps in the full-gather
        # result — the hoisted pattern the predictive path below uses
        # (see its backend-miscompile note: a fetched bank must never
        # feed the kernel from inside a cond branch). The repair here
        # IS the fallback: any checksum-failed row raises the
        # axis-agreed flag and every rank takes the exact full gather.
        fault_key = (
            inj.site_key("corr", step_idx) if inj is not None else None
        )
        bank = prefetch.gather_demand_payload(
            experts, plan, axis, pl, budget=budget, mode=pol.transport,
            num_slices=pol.num_slices, injector=inj, fault_key=fault_key,
        )
        bank_valid_v, bad_bank = prefetch.verify_rows(
            bank.fetched, bank.fetched_ids, bank.valid, table
        )
        n_bad = lax.psum(jnp.sum(bad_bank.astype(jnp.float32)), all_axes)
        fault_fb = n_bad > 0
        fallback = plan.overflow | fault_fb
        y_compact = _remap_and_run(
            d, bank.fetched, bank.fetched_ids, bank_valid_v
        )
        y = lax.cond(
            fallback, full_path, lambda experts, d: y_compact, experts, d
        )
        inj3 = (
            _injected_counts(inj, fault_key, budget, plan.valid)
            if inj is not None else jnp.zeros((3,), jnp.float32)
        )
        fstats = jnp.concatenate([
            inj3,
            jnp.zeros((1,), jnp.float32),  # injected_cache (no cache)
            jnp.sum(bad_bank.astype(jnp.float32))[None],
            # globally agreed flag: contribute 1/n_ranks so the final
            # psum over every mesh axis reports it once
            (fault_fb.astype(jnp.float32) / n_ranks)[None],
            jnp.zeros((1,), jnp.float32),  # mirror_divergence (sync_free)
            _per_src_detected(bad_bank, min(budget, local), g, p),
        ])
        return y, None, fstats

    # Predictive: the correction round + compact kernel run
    # UNCONDITIONALLY (the modeled cost anyway — and the cache wants the
    # fetched rows even on fallback); the cond only swaps in the exact
    # full-gather result when the miss set overflowed the correction
    # budget. Keeping the compact compute OUT of the cond also sidesteps
    # a backend miscompile observed when a branch closure feeds the
    # speculative bank into the kernel (the cond's hoisted-operand
    # lowering returned wrong values on some ranks).
    corr_key = inj.site_key("corr", step_idx) if inj is not None else None
    bank = prefetch.gather_demand_payload(
        experts, plan, axis, pl, budget=budget, mode=pol.transport,
        num_slices=pol.num_slices, injector=inj, fault_key=corr_key,
    )
    if validate:
        # cached/speculative faults were already repaired above (they
        # fell out of the exclusion set, so the correction round
        # re-fetched them); a fault in the correction bank itself has no
        # further round to fall to, so it raises the same axis-agreed
        # fallback flag the overflow path uses.
        bank_valid_v, bad_corr = prefetch.verify_rows(
            bank.fetched, bank.fetched_ids, bank.valid, table
        )
        n_bad_corr = lax.psum(
            jnp.sum(bad_corr.astype(jnp.float32)), all_axes
        )
        fault_fb = n_bad_corr > 0
        fallback = plan.overflow | fault_fb
    else:
        fault_fb = jnp.bool_(False)
        bank_valid_v = bank.valid
        fallback = plan.overflow
    if sync_free:
        # a drifted mirror forces the exact path too (the spec bank was
        # already masked out above; this swaps in the full gather)
        fallback = fallback | diverged_g
    cat = lambda c, s, b: jnp.concatenate([c, s, b], axis=0)
    fe_all = jax.tree.map(cat, cache_w, spec_bank.fetched, bank.fetched)
    ids_all = cat(cache_ids, spec_bank.fetched_ids, bank.fetched_ids)
    # verified validity throughout: checksum-failed (or divergence-
    # voided) rows never map into the compact bank (a re-fetched
    # duplicate id wins the remap) and score -inf in the cache insert
    # below (corrupt rows are evicted, not re-cached)
    valid_all = cat(cache_valid_v, spec_valid_eff, bank_valid_v)
    y_compact = _remap_and_run(d, fe_all, ids_all, valid_all)
    y = lax.cond(
        fallback,
        full_path,
        lambda experts, d: y_compact,
        experts, d,
    )
    # ---- residency-cache insert: keep the EMA-hottest rows of (current
    # cache | this step's fetches); ids stay unique because both fetch
    # rounds excluded the cache (and each other). Branch-independent:
    # fetched rows are bit-exact expert copies even on the fallback. ----
    if sync_free:
        # mirrored replay: every rank replays EVERY position's cache
        # bookkeeping from exchanged/mirrored inputs only — the derived
        # (masks, resid_all) schedules plus the STRUCTURAL (unverified)
        # carried validity, never the local checksum results, so all
        # mirrors agree bit-for-bit. Eviction scores read the PRE-step
        # mirror EMA (``m_ema`` — the fold moved to the per-step site in
        # ``forward_decode``, after this layer runs); it is mirror-shared,
        # so replay determinism is unchanged. A corrupt row that stays
        # cached is caught again at next step's consume-time verify and
        # re-fetched through the correction round — still exact, one
        # step later.
        def replay(q, resid_q, ema_q, cids_q, cvalid_q, mask_q):
            s_ids, s_valid, _ = prefetch.plan_from_bitmap(
                mask_q, q, g, local, sbudget
            )
            c_ids, c_valid, _ = prefetch.plan_from_bitmap(
                resid_q, q, g, local, cbudget
            )
            ids_q = jnp.concatenate([cids_q, s_ids, c_ids])
            valid_q = jnp.concatenate(
                [cvalid_q, s_valid & ~diverged_g, c_valid]
            )
            # per-peer exclusion: an excluded peer's rows are never
            # cached (they would go stale while the peer is distrusted)
            for peer in xp.exclude_peers:
                valid_q = valid_q & (ids_q // local != peer % g)
            score = jnp.where(valid_q, ema_q[ids_q], -jnp.inf)
            order_q = jnp.argsort(-score)[:n_cache]
            return ids_q[order_q], valid_q[order_q], order_q

        rep_ids, rep_valid, rep_order = jax.vmap(replay)(
            jnp.arange(g), resid_all, m_ema, m_cids, m_cvalid, masks
        )
        nc_ids = lax.dynamic_index_in_dim(rep_ids, p, 0, keepdims=False)
        nc_valid = lax.dynamic_index_in_dim(
            rep_valid, p, 0, keepdims=False
        )
        order = lax.dynamic_index_in_dim(rep_order, p, 0, keepdims=False)
    else:
        score = jnp.where(valid_all, new_ema[ids_all], -jnp.inf)
        order = jnp.argsort(-score)[:n_cache]
        nc_ids = ids_all[order]
        nc_valid = valid_all[order]
    nc_w = jax.tree.map(lambda w: jnp.take(w, order, axis=0), fe_all)
    n_new = jnp.sum(spec_bank.valid) + jnp.sum(bank.valid)
    evicted = jnp.maximum(
        jnp.sum(cache_valid) + n_new - jnp.sum(nc_valid), 0
    ).astype(jnp.float32)
    # honest counters on the overflow fallback: the full gather served
    # EVERY wanted remote row over the wire, so nothing counts as a hit
    # and the whole wanted set counts as missed (the cache insert still
    # runs, so evictions report either way)
    zero = jnp.float32(0.0)
    stats = jnp.where(
        fallback,
        jnp.stack([n_pred, zero, zero, n_want, evicted]),
        jnp.stack(
            [n_pred, n_spec, n_cache_hit,
             jnp.sum(bank.valid).astype(jnp.float32), evicted]
        ),
    )
    if sync_free:
        # predictor fields pass through UNCHANGED — the per-step mirror
        # fold in ``forward_decode`` overwrites them once for every
        # sync-free layer from the one exchanged mirror payload; this
        # layer only contributes its routed bitmaps to that fold.
        new_pred = prefetch.PredictState(
            prev=pred.prev,
            ema=pred.ema,
            cache_ids=rep_ids[None],
            cache_valid=rep_valid[None],
            cache=jax.tree.map(lambda w: w[None], nc_w),
            stats=stats[None],
            aff=pred.aff,
            posb=pred.posb,
            sig=pred.sig,
            sigw=pred.sigw,
            routed=routed[None],
        )
    else:
        new_pred = prefetch.PredictState(
            prev=new_prev[None],
            ema=new_ema[None],
            cache_ids=nc_ids[None],
            cache_valid=nc_valid[None],
            cache=jax.tree.map(lambda w: w[None], nc_w),
            stats=stats[None],
        )
    div_contrib = (
        diverged_g.astype(jnp.float32) / n_ranks if sync_free
        else jnp.float32(0.0)
    )
    if not validate:
        if sync_free:
            # unvalidated sync-free still reports: the divergence digest
            # ran, and the HealthMonitor needs its counter
            fstats = jnp.concatenate([
                jnp.zeros((faults.FAULT_STAT_BASE - 1,), jnp.float32),
                div_contrib[None],
                jnp.zeros((g,), jnp.float32),
            ])
            return y, new_pred, fstats
        return y, new_pred, None
    if inj is not None:
        inj3 = _injected_counts(
            inj, inj.site_key("spec", step_idx), sbudget, spec_bank.valid
        ) + _injected_counts(inj, corr_key, budget, plan.valid)
        inj_cache = jnp.sum((cache_tamper & cache_valid).astype(jnp.float32))
    else:
        inj3 = jnp.zeros((3,), jnp.float32)
        inj_cache = jnp.float32(0.0)
    detected = (
        jnp.sum(bad_cache.astype(jnp.float32))
        + jnp.sum(bad_spec.astype(jnp.float32))
        + jnp.sum(bad_corr.astype(jnp.float32))
    )
    # per-subgroup-position attribution: payload rows by the peer-major
    # bank layout, cache rows by the position owning the expert id
    per_src = (
        _per_src_detected(bad_spec, sbudget, g, p)
        + _per_src_detected(bad_corr, cbudget, g, p)
        + jnp.zeros((g,), jnp.float32).at[cache_ids // local].add(
            bad_cache.astype(jnp.float32)
        )
    )
    fstats = jnp.concatenate([
        inj3,
        inj_cache[None],
        detected[None],
        # globally agreed flags: contribute 1/n_ranks so the final psum
        # over every mesh axis reports each once
        (fault_fb.astype(jnp.float32) / n_ranks)[None],
        div_contrib[None],
        per_src,
    ])
    return y, new_pred, fstats


def _moe_apply(x2d, mp, sig: LayerSig, ctx: Ctx, gathered: dict, rows: int,
               pred=None):
    cfg, geom, xp = ctx.cfg, ctx.geom, ctx.xp
    moe = cfg.moe
    pl = geom.moe_placement
    assert moe is not None and pl is not None
    t = x2d.shape[0]
    e_pad = pl.num_padded
    if xp.capacity_from == "global":
        # Layout-invariant capacity (ROADMAP decision): derive the slot
        # budget per ROW from the *global* per-row token count and
        # restrict capacity competition to the row. Rows never split
        # across ranks under batch sharding, so every mesh reshape of the
        # same global batch drops the identical token set. (Sequence
        # sharding splits rows; the per-rank slice then gets a ceil-
        # divided share — deterministic across batch reshapes, not across
        # seq-shard degree changes.)
        row_tokens = 1 if ctx.decode else xp.seq_len
        cap_row = moe_lib.capacity_for(
            row_tokens, moe.num_experts, moe.top_k, xp.capacity_factor
        )
        if not ctx.decode and xp.seq_shards > 1:
            cap_row = -(-cap_row // xp.seq_shards)
        cap = rows * cap_row
        d = moe_lib.route_topk_rows(
            x2d.reshape(rows, -1, x2d.shape[-1]), mp["router"], moe.top_k,
            cap_row, num_real=moe.num_experts,
        )
    else:
        cap = moe_lib.capacity_for(
            t, moe.num_experts, moe.top_k, xp.capacity_factor
        )
        d = moe_lib.route_topk(
            x2d, mp["router"], moe.top_k, cap, num_real=moe.num_experts
        )
    aux = moe_lib.load_balance_loss(d, e_pad)
    y = None
    new_pred = None
    fstats = None

    if xp.mode == "replicated" or pl.group_size == 1:
        xe = moe_lib.dispatch_tokens(x2d, d, e_pad, cap)
        ex = mp["experts"]
        banks = (ex["w_gate"], ex["w_up"], ex["w_down"])
        if split_gemm_lib.default_dense_impl(xp.phase) == "pallas":
            # every expert is resident: the §4.2 kernel over the local
            # bank with an empty remote one (on a TPU; the jnp grouped
            # FFN below is the same math, cheaper in interpret mode)
            ye = split_gemm_lib.split_swiglu(
                xe, *banks, *(w[:0] for w in banks), impl="pallas"
            )
        else:
            ye = moe_lib.grouped_ffn(xe, *banks)
    elif demand_fetch_active(cfg, geom, xp, ctx.group):
        # route-before-gather: the routing above used only the LOCAL
        # router weights, so the expert gather can now be demand-driven.
        # For plain demand, gather_set excluded this layer's expert bank
        # from the prefetch pipeline and the whole fetch happens here;
        # predictive decode layers instead receive the SPECULATIVE round's
        # compact DemandBank from the pipeline (fetched under the
        # previous layer's compute) and only the correction fetch happens
        # here, after routing.
        if predictive_fetch_active(cfg, geom, xp, ctx.group):
            spec = gathered.get("moe/experts")
            assert isinstance(spec, prefetch.DemandBank), (
                "predictive-active layers must prefetch the speculative "
                "demand bank"
            )
            y, new_pred, fstats = _moe_demand_apply(
                x2d, mp["experts"], d, cap, ctx, spec_bank=spec, pred=pred
            )
        else:
            assert "moe/experts" not in gathered, (
                "demand-active layers must not prefetch the expert bank"
            )
            y, _, fstats = _moe_demand_apply(x2d, mp["experts"], d, cap, ctx)
    elif moe_split_active(geom, xp, ctx.group):
        # §4.2 split fast path: tokens dispatch in rotated canonical order
        # (resident experts first), the fused kernel consumes the
        # SplitBank's (resident, remote) trees as two operands — the
        # merged (e_pad, D, F) buffer of the branch below never exists.
        bank = gathered.get("moe/experts")
        assert bank is not None, "split-mode expert bank must be prefetched"
        roll = (
            lax.axis_index(geom.expert_axes[0]) % pl.subgroup_size
        ) * pl.local_count
        d = _rolled_dispatch(d, roll, e_pad, cap)
        xe = moe_lib.dispatch_tokens(x2d, d, e_pad, cap)
        lo, re = bank.local, bank.remote
        ye = split_gemm_lib.split_swiglu(
            xe,
            lo["w_gate"], lo["w_up"], lo["w_down"],
            re["w_gate"], re["w_up"], re["w_down"],
            # pallas_call has no VJP; the jnp formulation (still merge-free)
            # carries the ZeRO-style train gathers
            impl="jnp" if xp.phase == "train" else "pallas",
        )
    elif xp.mode == "dwdp":
        xe = moe_lib.dispatch_tokens(x2d, d, e_pad, cap)
        if geom.moe_exec == "gather":
            full = gathered.get("moe/experts")
            assert full is not None, "gather-mode experts must be prefetched"
            ye = moe_lib.grouped_ffn(
                xe, full["w_gate"], full["w_up"], full["w_down"]
            )
        else:
            ye = _rotate_moe(xe, mp["experts"], ctx)
    else:  # dep / hybrid expert path: all-to-all dispatch/combine
        xe = moe_lib.dispatch_tokens(x2d, d, e_pad, cap)
        ax, groups = _expert_collective(geom, xp)
        xr = lax.all_to_all(
            xe, ax, split_axis=0, concat_axis=1, tiled=True,
            axis_index_groups=groups,
        )
        yr = moe_lib.grouped_ffn(
            xr, mp["experts"]["w_gate"], mp["experts"]["w_up"],
            mp["experts"]["w_down"],
        )
        ye = lax.all_to_all(
            yr, ax, split_axis=1, concat_axis=0, tiled=True,
            axis_index_groups=groups,
        )
    if y is None:
        y = moe_lib.combine_tokens(ye, d, t)
    if "shared" in mp:
        y = y + _ffn_apply(x2d, mp["shared"], ctx, gathered.get("moe/shared"))
    return y, aux, new_pred, fstats


# ==========================================================================
# Recurrent / xLSTM blocks (with RG-LRU cross-shard fix-up).
# ==========================================================================
def _rec_apply(h, rp, ctx: Ctx, lstate):
    xp = ctx.xp
    if ctx.decode or not xp.seq_axes:
        state = lstate if ctx.decode else None
        out, new_state = recurrent_block(h, rp, state)
        keep = ctx.decode or ctx.capture_len
        return out, (new_state if keep else lstate)

    # seq-sharded prefill/train: linear-recurrence fix-up (DESIGN.md §2)
    assert len(xp.seq_axes) == 1, "RG-LRU seq sharding is single-axis"
    ax = xp.seq_axes[0]
    g = xp.mesh_sizes[ax]
    b = h.shape[0]
    branch = h @ rp["w_x"]
    kw = rp["conv_w"].shape[0]
    halo = lax.ppermute(
        branch[:, -(kw - 1):], ax, [(i, i + 1) for i in range(g - 1)]
    )  # shard 0 receives zeros = fresh conv state
    branch, _ = causal_conv1d(branch, rp["conv_w"], halo.astype(branch.dtype))
    A, h_loc = rglru_parts(branch, rp["w_r"], rp["w_i"], rp["a_param"])
    a_last, h_last = A[:, -1], h_loc[:, -1]
    ag = lax.all_gather(a_last, ax)   # (G,B,D)
    hg = lax.all_gather(h_last, ax)
    h0 = jnp.zeros_like(h_last)
    prefixes = [h0]
    for s_i in range(g - 1):
        h0 = ag[s_i] * h0 + hg[s_i]
        prefixes.append(h0)
    h0_mine = jnp.take(
        jnp.stack(prefixes), lax.axis_index(ax), axis=0
    )
    hfix = (h_loc + A * h0_mine[:, None]).astype(h.dtype)
    gate = jax.nn.gelu(h @ rp["w_gate"], approximate=True)
    out = (hfix * gate) @ rp["w_o"]
    return out, lstate


def _cell_apply(h, cp, sig: LayerSig, ctx: Ctx, lstate):
    state = lstate if ctx.decode else None
    fn = mlstm_block if sig.kind == BlockKind.MLSTM else slstm_block
    out, new_state = fn(h, cp, state)
    keep = ctx.decode or ctx.capture_len
    return out, (new_state if keep else lstate)


# ==========================================================================
# One layer.
# ==========================================================================
def apply_layer(x, lp, sig: LayerSig, ctx: Ctx, lstate, gathered: dict,
                pred=None):
    cfg = ctx.cfg
    eps = cfg.norm_eps
    h = rms_norm(x, lp["norm1"], eps)
    aux = jnp.float32(0.0)
    new_pred = None
    fstats = None
    if sig.kind in (BlockKind.GLOBAL_ATTN, BlockKind.LOCAL_ATTN):
        aw = gathered.get("attn", lp["attn"])
        if "attn" in gathered or not ctx.geom.attn_axes:
            out, lstate = _attn_full(h, aw, sig, ctx, lstate)
        elif _qgather_ok(ctx.geom, ctx.xp):
            out, lstate = _attn_decode_qgather(h, lp["attn"], sig, ctx, lstate)
        else:
            out = _attn_tp(h, lp["attn"], sig, ctx)
    elif sig.kind == BlockKind.RECURRENT:
        rp = gathered.get("rec", lp["rec"])
        out, lstate = _rec_apply(h, rp, ctx, lstate)
    else:
        cp = gathered.get("cell", lp["cell"])
        out, lstate = _cell_apply(h, cp, sig, ctx, lstate)
    x = x + out
    if "norm2" in lp:
        h2 = rms_norm(x, lp["norm2"], eps)
        b, s, dm = h2.shape
        h2f = h2.reshape(b * s, dm)
        if sig.is_moe:
            y, aux, new_pred, fstats = _moe_apply(
                h2f, lp["moe"], sig, ctx, gathered, rows=b, pred=pred
            )
        else:
            y = _ffn_apply(h2f, lp["ffn"], ctx, gathered.get("ffn"))
        x = x + y.reshape(b, s, dm)
    return x, lstate, aux, new_pred, fstats


# ==========================================================================
# The layer stack with prefetch double-buffering.
# ==========================================================================
def _fs_add(a, b):
    """None-safe fault-stats accumulation (None = layer not validated)."""
    if b is None:
        return a
    return b if a is None else a + b


def _run_stack(params, x, ctx: Ctx, states):
    model = ctx.model
    aux_total = jnp.float32(0.0)
    new_states: dict = {}
    new_preds: dict = {}
    fs_total = None
    preds_all = states.get("pred") if isinstance(states, dict) else None
    for group in model.plan:
        gp = params["layers"][group.name]
        gs = states["layers"][group.name] if states is not None else None
        ps = preds_all.get(group.name) if preds_all else None
        ctx.group = group.name  # scope per-layer-group policy overrides
        if group.scan and group.n_cycles > 1:
            x, ns, nps, aux, fs = _run_scan_group(group, gp, x, ctx, gs, ps)
        else:
            x, ns, nps, aux, fs = _run_unrolled(group, gp, x, ctx, gs, ps)
        new_states[group.name] = ns
        if nps:
            new_preds[group.name] = nps
        aux_total = aux_total + aux
        fs_total = _fs_add(fs_total, fs)
    return x, new_states, new_preds, aux_total, fs_total


def _run_unrolled(group, gp, x, ctx: Ctx, gs, ps=None):
    aux_total = jnp.float32(0.0)
    new_states = {}
    new_preds = {}
    fs_total = None
    for j, sig in enumerate(group.sigs):
        lp = gp[f"pos{j}"]
        pred = ps.get(f"pos{j}") if ps else None
        paths = gather_set(sig, ctx.geom, ctx.xp, ctx.cfg, group.name)
        gathered = (
            gather_layer(_extract(lp, paths), ctx, pred=pred) if paths else {}
        )
        lstate = gs[f"pos{j}"] if gs is not None else None
        x, ns, aux, npred, fs = apply_layer(
            x, lp, sig, ctx, lstate, gathered, pred=pred
        )
        new_states[f"pos{j}"] = ns
        if npred is not None:
            new_preds[f"pos{j}"] = npred
        aux_total = aux_total + aux
        fs_total = _fs_add(fs_total, fs)
    return x, new_states, new_preds, aux_total, fs_total


def _run_scan_group(group, gp, x, ctx: Ctx, gs, ps=None):
    sigs = group.sigs
    period = len(sigs)
    paths = [
        gather_set(s, ctx.geom, ctx.xp, ctx.cfg, group.name) for s in sigs
    ]
    pipelined = ctx.xp.mode in ("dwdp", "hybrid") and any(paths)
    ps = ps or {}

    def _pred_at(name, cyc):
        """Layer ``name``'s incoming PredictState for cycle ``cyc`` —
        read from the closure-captured stacked state: within one decode
        step every layer's input state is the PREVIOUS step's, so the
        layer-ahead speculative gather may index it before the layer
        runs."""
        if name not in ps:
            return None
        return jax.tree.map(
            lambda w: lax.dynamic_index_in_dim(w, cyc, 0, keepdims=False),
            ps[name],
        )

    g0 = {}
    pos0_g = None
    n_cycles = group.n_cycles
    if pipelined and paths[0]:
        pos0_g = _extract(gp["pos0"], paths[0])  # stacked (n_cycles, ...)
        first = jax.tree.map(lambda w: w[0], pos0_g)
        g0 = gather_layer(first, ctx, pred=_pred_at("pos0", jnp.int32(0)))

    def body(carry, xs):
        x, g = carry
        lp_all, st_all, pd_all, cyc = xs
        aux_c = jnp.float32(0.0)
        new_sts = {}
        new_pds = {}
        fs_c = None
        for j, sig in enumerate(sigs):
            lp = lp_all[f"pos{j}"]
            if pipelined:
                nj = (j + 1) % period
                nxt_paths = paths[nj]
                if not nxt_paths:
                    g_next = {}
                elif nj == 0:
                    # cross-cycle prefetch: index the closure-captured
                    # stacked bank at (cyc+1) mod n — a per-iteration
                    # dynamic slice instead of a whole-bank jnp.roll copy
                    nxt_raw = jax.tree.map(
                        lambda w: lax.dynamic_index_in_dim(
                            w, (cyc + 1) % n_cycles, 0, keepdims=False
                        ),
                        pos0_g,
                    )
                    g_next = gather_layer(
                        nxt_raw, ctx,
                        pred=_pred_at("pos0", (cyc + 1) % n_cycles),
                    )
                else:
                    g_next = gather_layer(
                        _extract(lp_all[f"pos{nj}"], nxt_paths), ctx,
                        pred=pd_all.get(f"pos{nj}") if pd_all else None,
                    )
            else:
                g_next = {}
                g = (
                    gather_layer(
                        _extract(lp, paths[j]), ctx,
                        pred=pd_all.get(f"pos{j}") if pd_all else None,
                    )
                    if paths[j]
                    else {}
                )
            lstate = st_all[f"pos{j}"] if st_all is not None else None
            x, ns, aux, npred, fs = apply_layer(
                x, lp, sig, ctx, lstate, g,
                pred=pd_all.get(f"pos{j}") if pd_all else None,
            )
            new_sts[f"pos{j}"] = ns
            if npred is not None:
                new_pds[f"pos{j}"] = npred
            g = g_next
            aux_c = aux_c + aux
            fs_c = _fs_add(fs_c, fs)
        return (x, g), (new_sts, new_pds, aux_c, fs_c)

    if ctx.xp.phase == "train":
        # remat the cycle: without this, backward saves every layer's
        # *gathered* full weight set (ZeRO-3's classic memory blow-up);
        # with it, backward re-gathers — trading one extra prefetch for
        # O(L x full-layer) HBM.
        body = jax.checkpoint(body)

    (x, _), (new_states, new_preds, auxs, fss) = lax.scan(
        body, (x, g0), (gp, gs, ps, jnp.arange(n_cycles))
    )
    fs_total = jnp.sum(fss, axis=0) if fss is not None else None
    return x, new_states, new_preds, jnp.sum(auxs), fs_total


# ==========================================================================
# Phase entry points (run inside shard_map).
# ==========================================================================
def _positions_offset(ctx: Ctx):
    xp = ctx.xp
    if xp.seq_axes:
        return _shard_index(xp, xp.seq_axes) * xp.local_seq
    return 0  # static: enables block-causal KV skipping


def _input_embed(params, batch, ctx: Ctx):
    cd = _compute_dtype(ctx.model)
    if "embeds" in batch:
        return batch["embeds"].astype(cd)
    emb = _embed_table(params, ctx)
    return emb[batch["tokens"]].astype(cd)


def _last_token_hidden(x, ctx: Ctx):
    xp = ctx.xp
    xl = x[:, -1]
    if xp.seq_axes:
        is_last = (_shard_index(xp, xp.seq_axes) == xp.seq_shards - 1)
        xl = xl * is_last.astype(xl.dtype)
        xl = lax.psum(xl, xp.seq_axes)
    return xl


def forward_prefill(params, batch, ctx: Ctx):
    ctx.q_offset = _positions_offset(ctx)
    x = _input_embed(params, batch, ctx)
    x, new_states, _, _, _ = _run_stack(params, x, ctx, None)
    x = rms_norm(x, params["final_norm"], ctx.cfg.norm_eps)
    xl = _last_token_hidden(x, ctx)
    out_state = None
    if ctx.capture_len:
        b = xl.shape[0]
        # the GLOBAL prefill depth (batch arrays are seq-sharded inside
        # shard_map, so their local length is not the decode position)
        out_state = {
            "pos": jnp.full((b,), ctx.xp.seq_len, jnp.int32),
            "layers": new_states,
        }
    if AXIS_MODEL in ctx.xp.batch_axes:
        # tokens are batch-sharded over the vocab axis: use the gathered
        # (train-style) head so each rank scores its own rows fully
        if ctx.cfg.tie_embeddings:
            head = _embed_table(params, ctx).T
        else:
            head = params["lm_head"]
            if ctx.geom.model_size > 1:
                head = lax.all_gather(head, AXIS_MODEL, axis=1, tiled=True)
        logits = (xl @ head).astype(jnp.float32)
        logits = softcap(logits, ctx.cfg.logit_softcap)
        out = {"last_logits": _mask_vocab_cols(logits, ctx, local=False)}
    else:
        logits = (xl @ _head_local(params, ctx)).astype(jnp.float32)
        logits = softcap(logits, ctx.cfg.logit_softcap)
        out = {"last_logits": _mask_vocab_cols(logits, ctx, local=True)}
    if out_state is not None:
        out["state"] = out_state
    return out


def _fold_mirrors(new_preds: dict, preds_in, ctx: Ctx) -> dict:
    """The sync-free per-STEP mirror fold: union every sync-free layer's
    routed bitmaps (returned through the transient ``PredictState.routed``
    channel), exchange them in ONE packed all-gather over the subgroup,
    fold once with :func:`prefetch.update_predictor` from the pre-step
    mirror state, and write the folded predictor fields into EVERY
    sync-free layer's outgoing state. The predictor models the rank, not
    the layer, so one fold per step replaces the old per-layer packed
    exchange — (n_moe_layers - 1) fewer metadata gathers per step, and
    the per-layer index traffic shrinks to the correction residual
    bitmap alone. Deterministic in the exchanged payload, so the mirrors
    stay bit-identical across ranks exactly as the per-layer fold did.

    No-op (returns ``new_preds`` unchanged) when no layer ran sync-free
    this step — plain predictive layers fold locally in-layer."""
    sf_keys = [
        (gname, pos)
        for gname, gdict in new_preds.items()
        for pos, ps in gdict.items()
        if ps.routed is not None
    ]
    if not sf_keys:
        return new_preds
    geom = ctx.geom
    pl = geom.moe_placement
    axis = geom.expert_axes[0]
    e_pad = pl.num_padded

    def _local_rows(leaf, nd):
        # strip the leading stack dims (scan cycles x rank shard) down
        # to the per-mirror view: (..., *leaf.shape[-nd:]) -> cycle 0
        return leaf.reshape((-1,) + leaf.shape[-nd:])[0]

    routed_u = None
    for gname, pos in sf_keys:
        r = new_preds[gname][pos].routed  # (1, rows, E) | (n, 1, rows, E)
        r = jnp.any(r.reshape((-1,) + r.shape[-2:]), axis=0)
        routed_u = r if routed_u is None else (routed_u | r)
    buckets = prefetch.position_buckets(ctx.pos)
    packed = prefetch.pack_mirror_payload(routed_u, buckets)
    all_packed = lax.all_gather(
        packed, axis, axis_index_groups=pl.axis_index_groups()
    )
    routed_all, buckets_all = prefetch.unpack_mirror_payload(
        all_packed, e_pad
    )
    # pre-step mirror state: identical across sync-free layers by
    # construction (cold init is uniform zeros; every later step writes
    # the same folded fields everywhere), so any layer's incoming state
    # seeds the fold
    g0, p0 = sf_keys[0]
    m = preds_in[g0][p0]
    new_prev, new_ema, new_aff, new_posb, new_sig, new_sigw = jax.vmap(
        prefetch.update_predictor
    )(
        _local_rows(m.ema, 2), _local_rows(m.aff, 3),
        _local_rows(m.posb, 3), _local_rows(m.sigw, 2),
        routed_all, buckets_all,
    )
    folded = {
        "prev": new_prev, "ema": new_ema, "aff": new_aff,
        "posb": new_posb, "sig": new_sig, "sigw": new_sigw,
    }

    def _bcast(v, like):
        return jnp.broadcast_to(
            v.reshape((1,) * (like.ndim - v.ndim) + v.shape), like.shape
        )

    out = {g: dict(d) for g, d in new_preds.items()}
    for gname, pos in sf_keys:
        ps = out[gname][pos]
        out[gname][pos] = ps._replace(
            routed=None,
            **{k: _bcast(v, getattr(ps, k)) for k, v in folded.items()},
        )
    return out


def forward_decode(params, batch, state, ctx: Ctx):
    assert AXIS_MODEL not in ctx.xp.batch_axes
    ctx.pos = state["pos"]
    token = batch["token"]
    x = _embed_decode(params, token, ctx)
    x, new_layer_states, new_preds, _, fstats = _run_stack(
        params, x, ctx, state
    )
    if new_preds:
        new_preds = _fold_mirrors(new_preds, state.get("pred"), ctx)
    x = rms_norm(x, params["final_norm"], ctx.cfg.norm_eps)
    logits = (x[:, 0] @ _w(_head_local(params, ctx), x)).astype(jnp.float32)
    logits = softcap(logits, ctx.cfg.logit_softcap)
    logits = _mask_vocab_cols(logits, ctx, local=True)
    # greedy sharded argmax over the vocab shards
    v_l = logits.shape[-1]
    val = jnp.max(logits, axis=-1)
    idx = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if ctx.geom.model_size > 1:
        off = lax.axis_index(AXIS_MODEL) * v_l
        vals = lax.all_gather(val, AXIS_MODEL)        # (G, B)
        idxs = lax.all_gather(idx + off, AXIS_MODEL)  # (G, B)
        best = jnp.argmax(vals, axis=0)
        nxt = jnp.take_along_axis(idxs, best[None], axis=0)[0]
    else:
        nxt = idx
    new_state = dict(state)
    new_state["layers"] = new_layer_states
    new_state["pos"] = state["pos"] + 1
    out = {"next_token": nxt[:, None], "state": new_state}
    if new_preds:
        new_state["pred"] = new_preds
        # per-step predictive counters [predicted, spec_hit, cache_hit,
        # miss, evicted] rows, summed over layers and (psum) over ranks
        # -> replicated
        pstates = jax.tree.leaves(
            new_preds,
            is_leaf=lambda t: isinstance(t, prefetch.PredictState),
        )
        stats = sum(
            jnp.sum(p.stats.reshape(-1, 5), axis=0) for p in pstates
        )
        out["pred_stats"] = lax.psum(stats, tuple(ctx.xp.mesh_sizes))
    if fstats is not None:
        # per-step fault counters (see faults.FAULT_STAT_NAMES + per-src
        # tail), summed over layers and (psum) over ranks -> replicated
        out["fault_stats"] = lax.psum(fstats, tuple(ctx.xp.mesh_sizes))
    return out


def _chunked_xent(x2d, head, labels, ctx: Ctx):
    """Memory-bounded sharded cross-entropy: scan over token chunks."""
    t, dm = x2d.shape
    nchunk = -(-t // XENT_CHUNK)
    pad = nchunk * XENT_CHUNK - t
    xpad = jnp.pad(x2d, ((0, pad), (0, 0)))
    lpad = jnp.pad(labels, (0, pad), constant_values=-1)
    v = ctx.cfg.vocab_size
    cap = ctx.cfg.logit_softcap

    @jax.checkpoint  # logits are recomputed in backward, never stored
    def body(carry, i):
        ls, cnt = carry
        xc = lax.dynamic_slice_in_dim(xpad, i * XENT_CHUNK, XENT_CHUNK, 0)
        lc = lax.dynamic_slice_in_dim(lpad, i * XENT_CHUNK, XENT_CHUNK, 0)
        logits = (xc @ head).astype(jnp.float32)
        logits = softcap(logits, cap)
        logits = jnp.where(jnp.arange(logits.shape[-1]) < v, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.clip(lc, 0, v - 1)[:, None], axis=-1
        )[:, 0]
        valid = (lc >= 0).astype(jnp.float32)
        ls = ls + jnp.sum((lse - ll) * valid)
        cnt = cnt + jnp.sum(valid)
        return (ls, cnt), None

    (ls, cnt), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(nchunk)
    )
    return ls, cnt


def forward_train(params, batch, ctx: Ctx):
    """Returns (loss_for_grad, metrics).

    ``loss_for_grad`` is the *local* (per-rank, unreduced) contribution
    divided by the global token count: differentiating it per rank and
    psum-ing grads in ``sync_grads`` yields exactly d(global mean)/dw.
    (Reducing the loss itself before grad would double-count through the
    psum transpose under check_vma=False.) ``metrics`` carry the properly
    psum-reduced scalars.
    """
    ctx.q_offset = _positions_offset(ctx)
    x = _input_embed(params, batch, ctx)
    x, _, _, aux, _ = _run_stack(params, x, ctx, None)
    x = rms_norm(x, params["final_norm"], ctx.cfg.norm_eps)
    b, s, dm = x.shape
    if ctx.cfg.tie_embeddings:
        head = _embed_table(params, ctx).T
    else:
        head = params["lm_head"]
        if ctx.geom.model_size > 1:
            head = lax.all_gather(head, AXIS_MODEL, axis=1, tiled=True)
    ls, cnt = _chunked_xent(
        x.reshape(b * s, dm), head, batch["labels"].reshape(-1), ctx
    )
    all_axes = tuple(ctx.xp.mesh_sizes)
    n_ranks = math.prod(ctx.xp.mesh_sizes.values())
    cnt_g = lax.stop_gradient(lax.psum(cnt, all_axes))
    denom = jnp.maximum(cnt_g, 1.0)
    # If tokens are replicated over idle mesh axes, both psum(ls) and
    # cnt_g carry the same replication factor — it cancels in the mean
    # and in the synced gradient alike, so no explicit correction needed.
    rep = n_ranks // max(1, ctx.xp.batch_shards * ctx.xp.seq_shards)
    loss_local = ls / denom + 0.01 * aux / n_ranks
    loss_g = lax.psum(ls, all_axes) / denom
    aux_g = lax.psum(aux, all_axes) / n_ranks
    return loss_local, {"loss": loss_g, "aux_loss": aux_g, "tokens": cnt_g / rep}


# ==========================================================================
# shard_map-wrapped step builders.
# ==========================================================================
def _grad_sync_axes(spec, mesh_axes: tuple[str, ...]) -> tuple[str, ...]:
    used: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, tuple):
            used.update(entry)
        else:
            used.add(entry)
    return tuple(a for a in mesh_axes if a not in used)


def sync_grads(grads, pspecs, mesh_axes: tuple[str, ...]):
    """psum each grad over the axes its param is replicated on."""

    def f(g, spec):
        axes = _grad_sync_axes(spec, mesh_axes)
        return lax.psum(g, axes) if axes else g

    return jax.tree.map(
        f, grads, pspecs, is_leaf=lambda x: isinstance(x, P)
    )


def _sharded_global_norm(grads, pspecs, mesh_axes, model: Model):
    """Global grad norm with every logical element counted exactly once:
    sharded leaves psum their sumsq over their shard axes; redundant
    expert copies (already grad-synced, hence identical) divide by R."""
    pl = model.geom.moe_placement
    r_fac = float(pl.redundancy) if pl is not None else 1.0
    terms = []

    def walk(g, spec, in_experts):
        if isinstance(g, dict):
            for k in g:
                walk(g[k], spec[k], in_experts or k == "experts")
            return
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        axes = tuple(
            a for a in mesh_axes if a not in _grad_sync_axes(spec, mesh_axes)
        )
        if axes:
            s = lax.psum(s, axes)
        terms.append(s / r_fac if in_experts else s)

    walk(grads, pspecs, False)
    return jnp.sqrt(sum(terms))


def sync_redundant_expert_grads(grads, model: Model, xp: ExecutionPlan):
    """Redundant placement (R > 1) stores each expert on R subgroups; the
    copies must train identically, so their grads are psum'd across the
    subgroups holding the same expert (ranks {p, p+G', p+2G', ...})."""
    pl = model.geom.moe_placement
    if pl is None or pl.redundancy == 1:
        return grads
    groups = [
        [p + s * pl.subgroup_size for s in range(pl.redundancy)]
        for p in range(pl.subgroup_size)
    ]
    ax = _axes_arg(model.geom.expert_axes)

    def fix(tree):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if k == "experts":
                    out[k] = jax.tree.map(
                        lambda g: lax.psum(g, ax, axis_index_groups=groups), v
                    )
                else:
                    out[k] = fix(v)
            return out
        return tree

    new = dict(grads)
    new["layers"] = fix(grads["layers"])
    return new


# ==========================================================================
# Predictive-fetch state lifecycle (decode only).
# ==========================================================================
def init_predict_state(model: Model, xp: ExecutionPlan) -> dict:
    """Cold :class:`prefetch.PredictState` tree for every
    predictive-active MoE layer — ``{group: {posJ: PredictState}}``
    (scan groups stacked over cycles), or ``{}`` when the plan has no
    predictive decode layers.

    Arrays carry a leading per-RANK dim (``prod(mesh_sizes)``): every
    rank routes its own tokens and caches its own fetched remote rows,
    so the state is genuinely per-device — sharded over ALL mesh axes by
    ``predict_state_pspecs``, never replicated. Sync-free layers
    additionally carry a per-SUBGROUP-POSITION dim after it (each rank
    mirrors the predictor + cache *bookkeeping* of every peer in its own
    subgroup; the cached WEIGHTS stay own-rows-only) plus the richer-
    predictor slots (aff/posb/sig/sigw). Cold state = empty predictor +
    invalid cache: the first step's speculative round fetches nothing
    and the correction round degenerates to the plain demand round (or
    its exact overflow fallback), so cold starts are bitwise-safe by
    construction."""
    cfg, geom = model.cfg, model.geom
    n_ranks = math.prod(xp.mesh_sizes.values())
    out: dict = {}
    for group in model.plan:
        gdict = {}
        for j, sig in enumerate(group.sigs):
            if not (
                sig.is_moe
                and predictive_fetch_active(cfg, geom, xp, group.name)
            ):
                continue
            pl = geom.moe_placement
            e_pad = pl.num_padded
            rows = resolve_cache_rows(cfg, geom, xp, group.name)
            dm, fe = cfg.d_model, cfg.moe.d_ff
            wdt = model.dtype
            if sync_free_active(cfg, geom, xp, group.name):
                gsz = pl.subgroup_size
                bl = max(1, xp.local_batch)
                nb = prefetch.N_POS_BUCKETS
                ps = prefetch.PredictState(
                    prev=jnp.zeros((n_ranks, gsz, e_pad), bool),
                    ema=jnp.zeros((n_ranks, gsz, e_pad), jnp.float32),
                    cache_ids=jnp.zeros((n_ranks, gsz, rows), jnp.int32),
                    cache_valid=jnp.zeros((n_ranks, gsz, rows), bool),
                    cache={
                        "w_gate": jnp.zeros((n_ranks, rows, dm, fe), wdt),
                        "w_up": jnp.zeros((n_ranks, rows, dm, fe), wdt),
                        "w_down": jnp.zeros((n_ranks, rows, fe, dm), wdt),
                    },
                    stats=jnp.zeros((n_ranks, 5), jnp.float32),
                    aff=jnp.zeros((n_ranks, gsz, bl, e_pad), jnp.float32),
                    posb=jnp.zeros((n_ranks, gsz, nb, e_pad), jnp.float32),
                    sig=jnp.zeros((n_ranks, gsz, 2, e_pad), jnp.float32),
                    sigw=jnp.zeros((n_ranks, gsz, 2), jnp.float32),
                )
            else:
                ps = prefetch.PredictState(
                    prev=jnp.zeros((n_ranks, e_pad), bool),
                    ema=jnp.zeros((n_ranks, e_pad), jnp.float32),
                    cache_ids=jnp.zeros((n_ranks, rows), jnp.int32),
                    cache_valid=jnp.zeros((n_ranks, rows), bool),
                    cache={
                        "w_gate": jnp.zeros((n_ranks, rows, dm, fe), wdt),
                        "w_up": jnp.zeros((n_ranks, rows, dm, fe), wdt),
                        "w_down": jnp.zeros((n_ranks, rows, fe, dm), wdt),
                    },
                    stats=jnp.zeros((n_ranks, 5), jnp.float32),
                )
            if group.scan:
                ps = jax.tree.map(
                    lambda w: jnp.broadcast_to(
                        w[None], (group.n_cycles,) + w.shape
                    ),
                    ps,
                )
            gdict[f"pos{j}"] = ps
        if gdict:
            out[group.name] = gdict
    return out


def attach_predict_state(state: dict, model: Model, xp: ExecutionPlan) -> dict:
    """Return ``state`` with a cold ``state["pred"]`` attached when the
    plan runs the predictive fetch anywhere (no-op otherwise). The ONE
    call sites need — the decode step threads and updates it from there."""
    pred = init_predict_state(model, xp)
    if not pred:
        return state
    state = dict(state)
    state["pred"] = pred
    return state


def predict_state_pspecs(model: Model, xp: ExecutionPlan) -> dict:
    """PartitionSpecs mirroring :func:`init_predict_state`: the leading
    per-rank dim shards over EVERY mesh axis (the state is per-device,
    not replicated), everything after it is local."""
    cfg, geom = model.cfg, model.geom
    ra = tuple(xp.mesh_sizes)
    out: dict = {}
    for group in model.plan:
        gdict = {}
        for j, sig in enumerate(group.sigs):
            if not (
                sig.is_moe
                and predictive_fetch_active(cfg, geom, xp, group.name)
            ):
                continue
            lead = (None,) if group.scan else ()

            def sp(nd):
                return P(*lead, ra, *([None] * nd))

            if sync_free_active(cfg, geom, xp, group.name):
                gdict[f"pos{j}"] = prefetch.PredictState(
                    prev=sp(2), ema=sp(2), cache_ids=sp(2),
                    cache_valid=sp(2),
                    cache={"w_gate": sp(3), "w_up": sp(3), "w_down": sp(3)},
                    stats=sp(1),
                    aff=sp(3), posb=sp(3), sig=sp(3), sigw=sp(2),
                )
            else:
                gdict[f"pos{j}"] = prefetch.PredictState(
                    prev=sp(1), ema=sp(1), cache_ids=sp(1),
                    cache_valid=sp(1),
                    cache={"w_gate": sp(3), "w_up": sp(3), "w_down": sp(3)},
                    stats=sp(1),
                )
        if gdict:
            out[group.name] = gdict
    return out


def build_inner_fns(model: Model, xp: ExecutionPlan, capture_len: int = 0):
    """Phase-appropriate function to run inside shard_map."""
    if xp.phase == "train":

        def inner(params, batch):
            ctx = Ctx(model=model, xp=xp)
            return forward_train(params, batch, ctx)

        return inner
    if xp.phase == "prefill":

        def inner(params, batch):
            ctx = Ctx(model=model, xp=xp, capture_len=capture_len)
            return forward_prefill(params, batch, ctx)

        return inner

    def inner(params, batch, state):
        ctx = Ctx(model=model, xp=xp)
        return forward_decode(params, batch, state, ctx)

    return inner


def make_step_fn(model: Model, xp: ExecutionPlan, mesh, *, capture_len: int = 0):
    """jit(shard_map(...)) step for the plan's phase.

    - train: (params, opt, batch, lr) -> (params, opt, metrics)
    - prefill: (params, batch) -> {"last_logits"[, "state"]}
      (capture_len > 0 additionally emits the decode state — the
       disaggregated ctx->gen KV transfer payload)
    - decode: (params, batch, state) -> {"next_token", "state"}
    """
    pspecs = model.param_pspecs()
    in_b = input_pspecs(model, xp)
    mesh_axes = tuple(xp.mesh_sizes)
    inner = build_inner_fns(model, xp, capture_len)

    if xp.phase == "train":
        from repro.optim.adamw import AdamWState, adamw_update

        def step(params, opt_state, batch, lr):
            (_, metrics), grads = jax.value_and_grad(
                lambda p: inner(p, batch), has_aux=True
            )(params)
            grads = sync_grads(grads, pspecs, mesh_axes)
            grads = sync_redundant_expert_grads(grads, model, xp)
            gn = _sharded_global_norm(grads, pspecs, mesh_axes, model)
            scale = jnp.minimum(1.0, 1.0 / jnp.maximum(gn, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
            new_params, new_opt = adamw_update(
                grads, opt_state, params, lr=lr, clip_norm=0.0
            )
            return new_params, new_opt, metrics

        opt_specs = AdamWState(step=P(), m=pspecs, v=pspecs)
        sharded = shard_map(
            step,
            mesh=mesh,
            in_specs=(pspecs, opt_specs, in_b, P()),
            out_specs=(
                pspecs,
                opt_specs,
                {"loss": P(), "aux_loss": P(), "tokens": P()},
            ),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    if xp.phase == "prefill":
        out_sp = output_pspecs(model, xp)
        if capture_len:
            out_sp = dict(out_sp)
            out_sp["state"] = state_pspecs(model, xp)
        sharded = shard_map(
            inner,
            mesh=mesh,
            in_specs=(pspecs, in_b),
            out_specs=out_sp,
            check_vma=False,
        )
        return jax.jit(sharded)

    st_specs = state_pspecs(model, xp)
    pred_specs = predict_state_pspecs(model, xp)
    if pred_specs:
        st_specs = dict(st_specs)
        st_specs["pred"] = pred_specs
    out_specs = {
        "next_token": P(xp.batch_spec(), None),
        "state": st_specs,
    }
    if pred_specs:
        out_specs["pred_stats"] = P()  # psum'd inside -> replicated
    if fault_stats_active(model, xp):
        out_specs["fault_stats"] = P()  # psum'd inside -> replicated
    sharded = shard_map(
        inner,
        mesh=mesh,
        in_specs=(pspecs, in_b, st_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    # donate the KV cache / recurrent state: serving updates it in place.
    # The outputs are pinned to NamedShardings spelled exactly as
    # ``state_shardings`` spells them, so the state a step returns and
    # the state the server commits (boot, warmup, admit) key ONE jit
    # executable whatever form JAX would normalize the specs to.
    return jax.jit(
        sharded, donate_argnums=(2,),
        out_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), out_specs,
            is_leaf=lambda s: isinstance(s, P),
        ),
    )


def state_shardings(model: Model, xp: ExecutionPlan, mesh) -> dict:
    """The decode step's state shardings (KV/recurrent slots plus the
    predictive-fetch state, when the plan runs it) — what the server
    commits its state to and what :func:`make_step_fn` pins the step's
    state output to. Optional ``PredictState`` fields are ``None``."""
    specs = dict(state_pspecs(model, xp))
    pred = predict_state_pspecs(model, xp)
    if pred:
        specs["pred"] = pred
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
