"""Disaggregated serving engine (paper §5 setting, CPU-demo scale).

- ``ContextServer``: runs DWDP (or DEP) prefill with KV capture — the
  captured decode state is the ctx->gen transfer payload.
- ``GenerationServer``: slot-based continuous batching over the decode
  step. Each slot has its own position (the per-row position machinery in
  core/execution); requests join whenever a slot frees, without draining
  the batch — the paper's independent-worker property.
- ``DisaggregatedEngine``: queues, rate-matching and metrics glue.

Real arrays throughout: this is what examples/serve_demo.py runs on CPU
with a reduced model; the cluster-scale behaviour is explored by
runtime/simulator.py with roofline-modelled service times.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import execution, roofline
from repro.core.strategy import (
    PolicyTable, degradation_ladder, make_execution_plan, resolve_policies,
)
from repro.configs.base import InputShape
from repro.models.cache import init_decode_state
from repro.models.transformer import Model
from repro.runtime.metrics import RequestRecord, ServingMetrics


def validate_restore_plan(snapshot_plan: Optional[dict],
                          current_plan: dict) -> None:
    """Reject restoring a ``snapshot_slot`` payload into a server whose
    active plan no longer matches the one the snapshot was taken under.

    A snapshot's KV/position layout is only re-admittable verbatim when
    the destination runs the SAME model at the SAME mesh sizes and
    cache length with the SAME policy table and exclusion set — e.g. a
    post-rank-death shrunk replica, a health-demoted ladder rung, or a
    different ``PolicyTable`` would all restore into a mismatched
    variant and silently corrupt the stream. Raises ``ValueError``
    naming every mismatched field; the serving scheduler converts that
    into a requeue-from-prompt. ``None`` (a pre-plan-stamp snapshot)
    passes — legacy payloads keep working within one server."""
    if snapshot_plan is None:
        return
    bad = [
        f"{k}: snapshot {snapshot_plan.get(k)!r} != active "
        f"{current_plan.get(k)!r}"
        for k in sorted(set(snapshot_plan) | set(current_plan))
        if snapshot_plan.get(k) != current_plan.get(k)
    ]
    if bad:
        raise ValueError(
            "snapshot_slot resume rejected — the destination's active "
            "plan differs from the snapshot's ("
            + "; ".join(bad)
            + "); requeue the request from its prompt instead"
        )


def variant_key(table: PolicyTable, shape: InputShape,
                excl: tuple = ()) -> tuple:
    """The pre-compiled forward-variant cache key: canonicalized policy
    table (``describe()`` — sorted ``to_dict()`` JSON, so two tables
    collide iff their ``to_dict()`` forms are equal) + the shape bucket
    the variant was compiled for + the peer-exclusion set. Everything
    else that shapes the lowered program (model, mesh, mode) is fixed
    per cache instance."""
    return (
        table.describe(),
        (shape.phase, shape.seq_len, shape.global_batch),
        tuple(int(p) for p in excl),
    )


class CountingStep:
    """A jitted step function with a call counter and a compile-cache
    probe, preserving the jit surface (``.trace``, ``.lower``) the AOT
    tests and the chip smoke run use.

    ``cache_size()`` reads the underlying jit executable cache — after a
    variant is warmed, the serving path asserts this number stays flat
    across policy switches (the zero-recompile contract)."""

    def __init__(self, fn):
        self._fn = fn
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self._fn(*args, **kwargs)

    @property
    def trace(self):
        return self._fn.trace

    @property
    def lower(self):
        return self._fn.lower

    def cache_size(self) -> int:
        return int(self._fn._cache_size())


class PolicyVariantCache:
    """Pre-compiled forward-variant cache for one server.

    Maps :func:`variant_key` -> ``(plan, CountingStep, wire model)``,
    built lazily (or eagerly via the warmup path) and retained LRU up to
    ``max_entries`` so an online scheduler can flip between policy
    tables without re-tracing: a switch to a cached+warmed variant costs
    a dict lookup. Eviction only drops COLD state (the jitted callable
    and its executables) — correctness never depends on an entry being
    present."""

    def __init__(self, model: Model, mesh, mesh_sizes, shape: InputShape,
                 *, mode: str, capacity_from: str = "local",
                 fault_spec=None, validate_fetch: bool = False,
                 capture_len: int = 0, max_entries: int = 16):
        self.model = model
        self._mesh = mesh
        self._mesh_sizes = mesh_sizes
        self.shape = shape
        self._mode = mode
        self._capacity_from = capacity_from
        self._fault_spec = fault_spec
        self._validate_fetch = validate_fetch
        self._capture_len = capture_len
        self.max_entries = max(1, int(max_entries))
        self._entries: dict = {}
        self.stats = {"hits": 0, "misses": 0, "evictions": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def compiles(self) -> int:
        """Total jit executables across cached variants — flat after
        warmup iff the serving path never recompiles."""
        return sum(step.cache_size() for _, step, _ in
                   self._entries.values())

    def get(self, table: PolicyTable, excl: tuple = (),
            shape: Optional[InputShape] = None):
        """The (plan, step, wire-bytes) variant for a policy table,
        building it on miss. ``shape`` overrides the cache's home shape
        bucket — the ctx server's pow2 prefill-length buckets key in
        through here (decode buckets keep the home shape and vary the
        table instead)."""
        shape = shape if shape is not None else self.shape
        key = variant_key(table, shape, excl)
        if key in self._entries:
            self.stats["hits"] += 1
            # refresh LRU position
            self._entries[key] = self._entries.pop(key)
            return self._entries[key]
        self.stats["misses"] += 1
        xp = make_execution_plan(
            self.model, shape, self._mesh_sizes, mode=self._mode,
            policy=table, capacity_from=self._capacity_from,
            fault_spec=self._fault_spec,
            validate_fetch=self._validate_fetch,
            exclude_peers=tuple(int(p) for p in excl),
        )
        step = CountingStep(execution.make_step_fn(
            self.model, xp, self._mesh, capture_len=self._capture_len
        ))
        entry = (
            xp, step, execution.gathered_wire_bytes_per_step(self.model, xp)
        )
        while len(self._entries) >= self.max_entries:
            self._entries.pop(next(iter(self._entries)))
            self.stats["evictions"] += 1
        self._entries[key] = entry
        return entry

    def adopt(self, table: PolicyTable, excl: tuple, entry,
              shape: Optional[InputShape] = None):
        """Seed the cache with an already-built variant (the server's
        boot-time plan) without charging a miss."""
        key = variant_key(table, shape if shape is not None else self.shape,
                          excl)
        self._entries.setdefault(key, entry)


class BudgetTuner:
    """Online speculative-budget resizing over pre-compiled rungs.

    Watches the measured per-step predictive counters (``pred_stats``:
    ``[predicted, spec_hit, cache_hit, miss, evicted]`` expert rows) and
    snaps the speculative/correction row budget to the nearest rung of
    :func:`repro.core.roofline.predictive_budget_rungs`:

    - miss fraction above ``raise_miss_frac`` -> the correction round is
      doing the work, the speculative round is under-provisioned: go up
      one rung;
    - speculative utilization (``spec_hit / predicted``) below
      ``lower_util`` while misses are rare -> the speculative round
      ships rows nobody routes to: come down one rung.

    ``min_dwell`` observed steps must pass between moves (one bursty
    step must not flap the budget), and every emitted budget is a rung
    value — so a serving engine that pre-compiled one variant per rung
    resizes with zero recompiles."""

    def __init__(self, rungs, *, start: Optional[int] = None,
                 raise_miss_frac: float = 0.25, lower_util: float = 0.5,
                 lower_miss_frac: float = 0.1, min_dwell: int = 4):
        rungs = tuple(sorted(int(r) for r in rungs))
        if not rungs:
            raise ValueError("BudgetTuner needs at least one rung")
        self.rungs = rungs
        if start is None:
            self.idx = min(len(rungs) - 1, 1)
        else:
            self.idx = min(
                range(len(rungs)), key=lambda i: abs(rungs[i] - start)
            )
        self.raise_miss_frac = raise_miss_frac
        self.lower_util = lower_util
        self.lower_miss_frac = lower_miss_frac
        self.min_dwell = min_dwell
        self._since = min_dwell  # free to act on the first signal

    @property
    def budget(self) -> int:
        return self.rungs[self.idx]

    def observe(self, pred_stats) -> Optional[int]:
        """Feed one decode step's measured counters; returns the new
        rung budget when a resize fires, else None."""
        if pred_stats is None:
            return None
        pred, spec_hit, cache_hit, miss, _ = (
            float(s) for s in pred_stats
        )
        denom = spec_hit + cache_hit + miss
        self._since += 1
        if denom <= 0 or self._since <= self.min_dwell:
            return None
        miss_frac = miss / denom
        util = spec_hit / pred if pred > 0 else 1.0
        if (miss_frac > self.raise_miss_frac
                and self.idx + 1 < len(self.rungs)):
            self.idx += 1
            self._since = 0
            return self.rungs[self.idx]
        if (miss_frac < self.lower_miss_frac and util < self.lower_util
                and self.idx > 0):
            self.idx -= 1
            self._since = 0
            return self.rungs[self.idx]
        return None


def _with_spec_budget(table: PolicyTable, budget: int) -> PolicyTable:
    """``table`` with every speculative-fetch moe_experts entry (family
    AND per-layer-group overrides) pinned to ``budget`` rows — the
    compile-stable spelling of one budget rung."""

    def upd(name, pol):
        if name == "moe_experts" and pol.fetch in (
                "predictive", "sync_free"):
            return dataclasses.replace(pol, budget=int(budget))
        return pol

    return dataclasses.replace(
        table,
        families=tuple((n, upd(n, p)) for n, p in table.families),
        overrides=tuple(
            (g, n, upd(n, p)) for g, n, p in table.overrides
        ),
    )


class OnlinePolicyScheduler:
    """Zero-recompile online policy switching (``--policy auto-online``).

    Drives the generation server's :meth:`GenerationServer.set_policy`
    between pre-compiled forward variants, re-resolving the PolicyTable
    from three online signals:

    - **batch-shape buckets** — the decode step always runs the compiled
      ``max_batch`` shape, but the roofline-optimal table depends on how
      many slots are ACTIVE (the resolver scores with the routed-row
      count). Active-slot counts are bucketed to powers of two; crossing
      a bucket boundary re-resolves immediately at the new bucket's row
      count.
    - **measured hit-rate drift** — the served ``pred_stats`` split
      (speculative hits vs residency-cache hits vs correction rows) is
      EMA-tracked, quantized, and replayed into
      :func:`repro.core.strategy.resolve_policies` via ``hit_rates=``
      every ``interval`` decode steps; drifted rates can flip the
      resolved winner (e.g. sync_free -> demand when the predictor
      stops hitting) including per-layer-group overrides.
    - **speculative-budget resizing** — a :class:`BudgetTuner` snaps the
      speculative/correction row budget to the nearest pre-compiled rung
      (:func:`repro.core.roofline.predictive_budget_rungs`).

    Every emitted table is canonical (same resolver, quantized inputs),
    so a revisited operating point hits the variant cache — after
    :meth:`DisaggregatedEngine.warmup` the whole decision loop runs with
    ZERO recompiles. The scheduler only acts at degradation-ladder
    level 0: a health-demoted server belongs to the HealthMonitor until
    it re-promotes."""

    def __init__(self, model: Model, mesh_sizes, shape: InputShape, *,
                 interval: int = 8, ema_decay: float = 0.8, hw=None,
                 tuner: Optional[BudgetTuner] = None):
        self.model = model
        self.mesh_sizes = dict(mesh_sizes)
        self.shape = shape
        self.interval = max(1, int(interval))
        self.ema_decay = ema_decay
        self.hw = hw
        self.tuner = tuner
        self._tuner_resolved = tuner is not None
        self._hit_ema: Optional[tuple] = None  # (predict_hit, cache_hit)
        self._bucket: Optional[int] = None
        self._steps = 0
        self._resolved: dict = {}  # (bucket, quantized rates) -> table

    # -- signals ---------------------------------------------------------

    def _bucket_of(self, active_rows: int) -> int:
        b = 1
        while b < active_rows:
            b *= 2
        return min(b, self.shape.global_batch)

    def _observe_rates(self, pred_stats) -> None:
        if pred_stats is None:
            return
        _, spec_hit, cache_hit, miss, _ = (float(s) for s in pred_stats)
        denom = spec_hit + cache_hit + miss
        if denom <= 0:
            return
        # factor the measured split the way the roofline composes it:
        # (1 - cache_hit) * (1 - predict_hit) = correction fraction
        cache = cache_hit / denom
        non_cache = spec_hit + miss
        predict = spec_hit / non_cache if non_cache > 0 else 1.0
        rates = (predict, cache)
        if self._hit_ema is None:
            self._hit_ema = rates
        else:
            d = self.ema_decay
            self._hit_ema = tuple(
                d * e + (1.0 - d) * r
                for e, r in zip(self._hit_ema, rates)
            )

    def _quantized_rates(self) -> Optional[tuple]:
        """EMA rates on a 0.05 grid — the resolver-cache key, so jitter
        between steps cannot mint a new table per step."""
        if self._hit_ema is None:
            return None
        return tuple(round(r * 20) / 20 for r in self._hit_ema)

    # -- resolution ------------------------------------------------------

    def _resolve(self, bucket: int) -> PolicyTable:
        q = self._quantized_rates()
        key = (bucket, q)
        if key not in self._resolved:
            shape = dataclasses.replace(self.shape, global_batch=bucket)
            hit_rates = None
            if q is not None:
                predict, cache = q
                groups = set(roofline.layer_group_names(self.model.cfg))
                hit_rates = {
                    g: {"predict_hit": predict, "cache_hit": cache}
                    for g in groups
                }
            self._resolved[key] = resolve_policies(
                self.model, shape, self.mesh_sizes, "auto",
                hw=self.hw, hit_rates=hit_rates,
            )
        return self._resolved[key]

    def _ensure_tuner(self, gen: "GenerationServer") -> None:
        if self._tuner_resolved:
            return
        self._tuner_resolved = True
        cfg, pl = self.model.cfg, self.model.geom.moe_placement
        if cfg.moe is None or pl is None or pl.subgroup_size <= 1:
            return
        rows = max(1, gen.xp.local_batch)
        rungs = roofline.predictive_budget_rungs(
            rows * cfg.moe.top_k, cfg.moe.num_experts, pl.local_count
        )
        start = gen.xp.policies.family("moe_experts").budget or None
        self.tuner = BudgetTuner(rungs, start=start)

    def _snap_budget(self, table: PolicyTable) -> PolicyTable:
        if self.tuner is None:
            return table
        return _with_spec_budget(table, self.tuner.budget)

    # -- the decision loop ----------------------------------------------

    def step(self, gen: "GenerationServer",
             active_rows: int) -> Optional[str]:
        """One pre-decode-step decision: returns "switch" / "resize" /
        None (what, if anything, the server moved to)."""
        if gen.level != 0:
            return None
        self._ensure_tuner(gen)
        self._steps += 1
        self._observe_rates(gen.last_pred_stats)
        resized = (
            self.tuner.observe(gen.last_pred_stats) is not None
            if self.tuner is not None else False
        )
        bucket = self._bucket_of(max(1, active_rows))
        boundary = bucket != self._bucket
        if boundary or self._steps % self.interval == 0 or resized:
            self._bucket = bucket
            table = self._snap_budget(self._resolve(bucket))
            if gen.set_policy(table):
                return "resize" if resized and not boundary else "switch"
        return None

    def candidate_tables(self, gen: "GenerationServer") -> list:
        """The tables a warmup pass should pre-compile: the resolved
        table per batch bucket (at default drift) x the budget rungs,
        deduplicated, capped at the variant cache size so warming never
        evicts what it just compiled."""
        self._ensure_tuner(gen)
        out, seen = [], set()
        budgets: tuple = (None,)
        if self.tuner is not None:
            budgets = (None, *self.tuner.rungs)
        bucket, buckets = 1, []
        while bucket <= self.shape.global_batch:
            buckets.append(bucket)
            bucket *= 2
        for b in buckets:
            base = self._resolve(b)
            for budget in budgets:
                t = base if budget is None else _with_spec_budget(
                    base, budget
                )
                d = t.describe()
                if d not in seen:
                    seen.add(d)
                    out.append(t)
        return out[: gen.variants.max_entries]


def _resolve_policy(policy, *, prefetch="allgather", weight_layout=None,
                    expert_fetch="all", demand_budget=0, cache_budget=0):
    """Server-level policy resolution: an explicit ``policy`` (a
    PolicyTable, per-family dict, spec string, or "auto") wins; otherwise
    the simple per-knob kwargs spell a uniform table — WITHOUT routing
    through the deprecated make_execution_plan aliases, so internal
    callers stay warning-free."""
    if policy is not None:
        return policy
    return PolicyTable.uniform(
        layout=weight_layout if weight_layout is not None else "split",
        fetch=expert_fetch,
        transport=prefetch,
        budget=demand_budget,
        cache_budget=cache_budget,
    )


def _resolve_policy_table(model, shape, mesh_sizes, policy) -> PolicyTable:
    """A CONCRETE PolicyTable for the variant cache's canonical key:
    explicit tables pass through; dicts/specs/"auto"/"auto-online" run
    through :func:`resolve_policies` (idempotent with what
    make_execution_plan resolves internally)."""
    if isinstance(policy, PolicyTable):
        return policy
    return resolve_policies(model, shape, mesh_sizes, policy)


@dataclasses.dataclass
class Request:
    req_id: int
    tokens: np.ndarray        # (prompt_len,)
    target_len: int           # output tokens to generate
    arrival: float = 0.0

    def __post_init__(self):
        # fail at construction, not as downstream shape garbage
        self.tokens = np.asarray(self.tokens)
        if self.tokens.ndim != 1 or self.tokens.size == 0:
            raise ValueError(
                f"Request {self.req_id}: tokens must be a non-empty 1-d "
                f"prompt, got shape {self.tokens.shape}"
            )
        if int(self.target_len) < 1:
            raise ValueError(
                f"Request {self.req_id}: target_len must be >= 1 "
                f"(the prefill emits the first token), got {self.target_len}"
            )


class HealthMonitor:
    """Per-peer fault-pressure tracker with hysteresis.

    Consumes the per-source-position detected tail of each decode
    step's fault-stats vector; keeps an EMA of the "this peer served a
    bad row this step" event per peer. A peer whose EMA crosses
    ``demote_threshold`` requests a ladder demotion (predictive ->
    demand -> all-gather: each level leans less on per-peer payload
    rounds); once EVERY peer's EMA falls below ``promote_threshold``
    the monitor requests re-promotion. ``min_dwell`` steps must pass
    between transitions so one bad step cannot flap the policy."""

    def __init__(self, *, decay: float = 0.7, demote_threshold: float = 0.5,
                 promote_threshold: float = 0.1, min_dwell: int = 2):
        if not 0.0 < decay < 1.0:
            raise ValueError(f"decay must be in (0, 1), got {decay}")
        if promote_threshold >= demote_threshold:
            raise ValueError(
                "promote_threshold must sit below demote_threshold "
                f"(hysteresis), got {promote_threshold} >= {demote_threshold}"
            )
        self.decay = decay
        self.demote_threshold = demote_threshold
        self.promote_threshold = promote_threshold
        self.min_dwell = min_dwell
        self.ema = np.zeros(0)
        self._since_move = min_dwell  # free to act immediately

    def observe(self, detected_by_peer) -> Optional[str]:
        """Feed one step's per-peer detected counts; returns "demote",
        "promote", or None."""
        ev = (np.asarray(detected_by_peer, np.float64) > 0).astype(np.float64)
        if self.ema.shape != ev.shape:
            self.ema = np.zeros_like(ev)
        self.ema = self.decay * self.ema + (1.0 - self.decay) * ev
        self._since_move += 1
        if self._since_move <= self.min_dwell or self.ema.size == 0:
            return None
        if np.max(self.ema) > self.demote_threshold:
            self._since_move = 0
            return "demote"
        if np.max(self.ema) < self.promote_threshold:
            self._since_move = 0
            return "promote"
        return None

    def worst_peer(self) -> Optional[int]:
        """Subgroup position with the highest fault-pressure EMA (None
        before any observation) — the peer the ladder's per-peer
        exclusion rung drops from the speculative/cache plans."""
        if self.ema.size == 0:
            return None
        return int(np.argmax(self.ema))

    def bad_peers(self) -> tuple:
        """The peer SET the ladder's exclusion rung drops from the
        speculative/cache plans: every subgroup position whose
        fault-pressure EMA sits above ``demote_threshold``, hottest
        first. Falls back to the single worst peer when a demotion
        fired on a step whose decay already pulled every EMA back under
        the threshold. Never names every position — at least one peer
        stays in the speculative schedule, so the exclusion rung
        degrades toward (not past) plain demand fetch."""
        if self.ema.size == 0:
            return ()
        order = np.argsort(-self.ema, kind="stable")
        hot = [int(p) for p in order if self.ema[p] > self.demote_threshold]
        if not hot:
            hot = [int(order[0])]
        return tuple(hot[: max(1, self.ema.size - 1)])


class ContextServer:
    """Prefill worker: returns (first_token, captured decode state).

    Prompt lengths are served from pow2 seq-len BUCKETS: each configured
    bucket is one pre-compilable variant of the prefill step, keyed into
    the same :func:`variant_key` cache the decode server's policy
    variants use (the shape leg of the key varies instead of the table).
    ``prefill_len`` is the home bucket (and the only one by default —
    the pre-bucket behaviour); ``prefill_buckets`` adds more lengths,
    each a power of two. :meth:`warmup` pre-compiles every bucket, so
    serving mixed prompt lengths never traces on the request path."""

    def __init__(self, model: Model, mesh, mesh_sizes, *, mode="dwdp",
                 prefill_len: int, cache_len: int, prefetch="allgather",
                 weight_layout: Optional[str] = None,
                 capacity_from: str = "local",
                 expert_fetch: str = "all", demand_budget: int = 0,
                 cache_budget: int = 0, policy=None,
                 fault_spec=None, validate_fetch: bool = False,
                 prefill_buckets: tuple = ()):
        self.model = model
        self.prefill_len = prefill_len
        for b in prefill_buckets:
            b = int(b)
            if b < 1 or b & (b - 1):
                raise ValueError(
                    f"prefill_buckets must be powers of two, got {b}"
                )
        self.prefill_lens = tuple(sorted(
            {int(prefill_len), *(int(b) for b in prefill_buckets)}
        ))
        shape = InputShape("ctx", prefill_len, 1, "prefill")
        self._table = _resolve_policy_table(
            model, shape, mesh_sizes,
            _resolve_policy(
                policy, prefetch=prefetch, weight_layout=weight_layout,
                expert_fetch=expert_fetch, demand_budget=demand_budget,
                cache_budget=cache_budget,
            ),
        )
        self.variants = PolicyVariantCache(
            model, mesh, mesh_sizes, shape, mode=mode,
            capacity_from=capacity_from, fault_spec=fault_spec,
            validate_fetch=validate_fetch, capture_len=cache_len,
            max_entries=max(16, len(self.prefill_lens)),
        )
        self.xp, self.step, self.gather_bytes = self.bucket(prefill_len)

    def bucket(self, length: int):
        """The (plan, step, wire-bytes) variant of one prefill-length
        bucket (built on first use; warm after :meth:`warmup`)."""
        return self.variants.get(
            self._table,
            shape=InputShape("ctx", int(length), 1, "prefill"),
        )

    def warmup(self, params) -> None:
        """Trace+compile the prefill step of EVERY configured bucket off
        the serving path (the first real request of any bucketed length
        then hits a warm jit cache)."""
        for length in self.prefill_lens:
            _, step, _ = self.bucket(length)
            if step.calls == 0:
                self.prefill(params, np.zeros(length, np.int32))
                step.calls = 0

    def prefill_logits(self, params, tokens: np.ndarray) -> jax.Array:
        """Last-position logits ``(1, vocab_pad)`` of one prompt of ANY
        length — for checks against a reference, off the serving path:
        a length outside the bucket set builds and compiles its own
        variant on first use."""
        _, step, _ = self.bucket(len(tokens))
        row = jnp.asarray(np.asarray(tokens)[None, :], jnp.int32)
        return step(params, {"tokens": row})["last_logits"]

    def prefill(self, params, tokens: np.ndarray):
        """tokens: (prompt_len,) -> (first_token, state). The prompt
        length must exactly match a configured bucket (the request
        generator packs/clips); variable lengths beyond the bucket set
        are exercised by the cluster simulator."""
        length = len(tokens)
        assert length in self.prefill_lens, (length, self.prefill_lens)
        self.xp, self.step, self.gather_bytes = self.bucket(length)
        row = jnp.asarray(tokens[None, :], jnp.int32)
        out = self.step(params, {"tokens": row})
        logits = out["last_logits"]
        first = int(jnp.argmax(logits[0]))
        return first, out["state"]


class GenerationServer:
    """Slot-based continuous-batching decode worker."""

    def __init__(self, model: Model, mesh, mesh_sizes, *, mode="dep",
                 max_batch: int, cache_len: int,
                 weight_layout: Optional[str] = None,
                 capacity_from: str = "local",
                 expert_fetch: str = "all", demand_budget: int = 0,
                 cache_budget: int = 0, policy=None,
                 fault_spec=None, validate_fetch: bool = False,
                 variant_cache_size: int = 16):
        self.model = model
        self.max_batch = max_batch
        self.cache_len = cache_len
        shape = InputShape("gen", cache_len, max_batch, "decode")
        self._mesh = mesh
        self._mesh_sizes = mesh_sizes
        self._mode = mode
        self._shape = shape
        self._capacity_from = capacity_from
        self.fault_spec = fault_spec
        self.validate_fetch = validate_fetch
        # every (policy table, exclusion set) the server runs — the boot
        # table, degradation-ladder rungs, online-scheduler switches —
        # is one entry of the pre-compiled forward-variant cache; a
        # switch to a warmed entry costs a dict lookup, zero recompiles
        self.variants = PolicyVariantCache(
            model, mesh, mesh_sizes, shape, mode=mode,
            capacity_from=capacity_from, fault_spec=fault_spec,
            validate_fetch=validate_fetch, max_entries=variant_cache_size,
        )
        self.xp, self.step, self.gather_bytes = self.variants.get(
            _resolve_policy_table(
                model, shape, mesh_sizes,
                _resolve_policy(
                    policy, weight_layout=weight_layout,
                    expert_fetch=expert_fetch, demand_budget=demand_budget,
                    cache_budget=cache_budget,
                ),
            )
        )
        self.excl: tuple = ()
        # graceful-degradation ladder over the resolved policy table:
        # level 0 is the configured table; each further level leans one
        # notch less on per-peer payload rounds (predictive/sync_free ->
        # per-peer exclusion -> demand -> all-gather). Plans/steps are
        # built lazily per (table, excluded peers) via the variant
        # cache; see set_level for the predictive-state handoff.
        self.ladder = degradation_ladder(self.xp.policies)
        self.level = 0
        self.state = self._committed(execution.attach_predict_state(
            init_decode_state(model, max_batch, cache_len), model, self.xp
        ), self.xp)
        # bytes of one expert's weight rows — converts the predictive
        # fetch's per-step row counters into the byte counters the
        # serving metrics report
        cfg = model.cfg
        self.expert_bytes = (
            3 * cfg.d_model * cfg.moe.d_ff * jnp.dtype(model.dtype).itemsize
            if cfg.moe is not None else 0
        )
        self.last_pred_stats: Optional[np.ndarray] = None
        self.last_fault_stats: Optional[np.ndarray] = None
        # inactive slots: pos points at an empty cache; emitted tokens junk
        self.slot_req: list[Optional[int]] = [None] * max_batch
        self.slot_remaining = np.zeros(max_batch, np.int64)
        self.cur_token = self._committed_token(
            jnp.zeros((max_batch, 1), jnp.int32)
        )

    def _committed(self, state, xp):
        """The decode state committed to the step's pinned OUTPUT
        shardings (:func:`execution.state_shardings`). The jit executable
        cache keys on input shardings, spelling included, so a state
        built on the host or updated by an eager op (``admit``) would
        compile a throwaway executable distinct from the steady-state
        one whose inputs are the previous step's outputs — committing
        here gives boot, warmup, admit and serving calls ONE signature,
        which is what lets the warmup pass guarantee zero serving-path
        recompiles. A leaf already on its sharding is not copied."""
        shardings = execution.state_shardings(self.model, xp, self._mesh)
        if "pred" not in state:
            shardings.pop("pred", None)
        return jax.tree.map(
            # optional PredictState leaves (the richer-predictor fields)
            # are None in plain predictive mode — in both the state and
            # its sharding tree — and stay None
            lambda x, s: x if x is None else jax.device_put(x, s),
            state, shardings,
            is_leaf=lambda x: x is None,
        )

    def _committed_token(self, tok):
        """The token row committed to the decode step's pinned
        next_token output sharding (same signature-stability argument as
        :meth:`_committed`)."""
        return jax.device_put(
            tok, NamedSharding(self._mesh, P(self.xp.batch_spec(), None))
        )

    @property
    def fetch_label(self) -> str:
        """The current ladder rung's label ("sync_free" / "predictive" /
        "<root>+excl" / "demand" / "all" / "reshard")."""
        return self.ladder[self.level][0]

    @property
    def max_silent_level(self) -> int:
        """The deepest ladder level FAIL-SILENT demotions may reach:
        the all-gather floor. The terminal ``"reshard"`` rung is the
        fail-stop response — only an explicit rank-death quarantine
        (the serving layer's ``kill_rank`` path) steps past this cap,
        and it does so by swapping in a shrunk-mesh standby engine, not
        by ``set_level``."""
        top = len(self.ladder) - 1
        if self.ladder[top][0] == "reshard":
            return max(0, top - 1)
        return top

    def restore_plan(self) -> dict:
        """The active plan descriptor stamped into every
        :meth:`snapshot_slot` payload and checked by
        :func:`validate_restore_plan` on re-admission."""
        return {
            "model": self.model.cfg.name,
            "mesh": tuple(sorted(
                (str(a), int(s)) for a, s in self._mesh_sizes.items()
            )),
            "cache_len": int(self.cache_len),
            "policies": self.xp.policies.describe(),
            "excl": tuple(int(p) for p in self.excl),
        }

    def set_level(self, level: int, bad_peers: tuple = ()) -> bool:
        """Move to a degradation-ladder level (clamped); returns whether
        the level changed. Swaps in that level's (plan, step fn, wire
        model) — built lazily on first use — and re-attaches a COLD
        predictive state shaped for the new plan: the residency cache /
        predictor do not survive a policy change (their budgets differ),
        which is exactly the safe behaviour when a peer went bad. KV /
        recurrent slot state carries over untouched.

        A per-peer-exclusion rung (excl ``None`` in the ladder) is
        instantiated against ``bad_peers`` — the HealthMonitor's
        over-threshold subgroup positions (:meth:`HealthMonitor.
        bad_peers`) — and cached per (table, exclusion set) in the
        variant cache, so re-entering the rung against a different bad
        set rebuilds the plan for exactly those peers."""
        level = max(0, min(int(level), len(self.ladder) - 1))
        if level == self.level:
            return False
        _, table, excl = self.ladder[level]
        if excl is None:
            excl = tuple(bad_peers)
        self._swap(table, tuple(int(p) for p in excl))
        self.level = level
        return True

    def set_policy(self, table: PolicyTable) -> bool:
        """Online policy SWITCH (the auto-online scheduler's entry
        point): move the decode step to a different resolved policy
        table — a pre-compiled variant when warmed, a lazy build
        otherwise — and rebase the degradation ladder on it. Only legal
        at ladder level 0 (a health-degraded server keeps its rung until
        the monitor re-promotes); returns whether anything changed."""
        if self.level != 0:
            return False
        if (table.describe() == self.xp.policies.describe()
                and not self.excl):
            return False
        self._swap(table, ())
        self.ladder = degradation_ladder(table)
        self.level = 0
        return True

    def _swap(self, table: PolicyTable, excl: tuple) -> None:
        """Install the (table, exclusion set) variant and re-attach a
        COLD predictive state shaped for it (see set_level)."""
        self.xp, self.step, self.gather_bytes = self.variants.get(
            table, excl
        )
        self.excl = excl
        bare = {k: v for k, v in self.state.items() if k != "pred"}
        self.state = self._committed(execution.attach_predict_state(
            bare, self.model, self.xp
        ), self.xp)
        self.last_pred_stats = None
        self.last_fault_stats = None

    def warmup(self, params, tables=()) -> int:
        """Pre-compile forward variants OFF the serving path: for each
        policy table (plus the currently-installed one) build its plan
        and run one decode step on a THROWAWAY state (the decode jit
        donates its state argument, so warming must not consume the live
        slots). After this, switching to any warmed table is
        trace-free and compile-free — the no-recompile contract the
        serving tests assert via ``variants.compiles()``. Returns the
        number of variants compiled."""
        compiled = 0
        tok = self._committed_token(
            jnp.zeros((self.max_batch, 1), jnp.int32)
        )
        seen = set()
        for table in (self.xp.policies, *tables):
            key = variant_key(table, self._shape, ())
            if key in seen:
                continue
            seen.add(key)
            xp, step, _ = self.variants.get(table, ())
            if step.calls:
                continue
            state = self._committed(execution.attach_predict_state(
                init_decode_state(self.model, self.max_batch,
                                  self.cache_len),
                self.model, xp,
            ), xp)
            # committed inputs and the step's pinned outputs share one
            # spelling, so this one call warms the boot, post-switch and
            # steady-state signatures alike
            step(params, {"token": tok}, state)
            step.calls = 0
            compiled += 1
        return compiled

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def admit(self, slot: int, req_id: int, first_token: int, ctx_state):
        """Install a context-server state into one batch slot. Scan groups
        carry a leading cycle axis, so the batch axis is 1 there. The
        predictive-fetch state ("pred" — per-RANK predictor + residency
        cache, shared by every slot) is untouched: admitting a request
        must not flush the cache the other slots are hitting.

        A ``snapshot_slot`` payload carries its origin plan descriptor
        under ``"plan"`` and is validated against the ACTIVE plan
        before any state is written (``validate_restore_plan`` raises
        ``ValueError`` on mismatch — the serving layer converts that
        into a requeue-from-prompt)."""
        if isinstance(ctx_state, dict) and "plan" in ctx_state:
            validate_restore_plan(ctx_state["plan"], self.restore_plan())
        new_layers = {}
        for group in self.model.plan:
            stacked = group.scan and group.n_cycles > 1
            bax = 1 if stacked else 0

            def write(dst, src, bax=bax):
                idx = (slice(None),) * bax + (slot,)
                src_row = src[(slice(None),) * bax + (0,)]
                return dst.at[idx].set(src_row.astype(dst.dtype))

            new_layers[group.name] = jax.tree.map(
                write,
                self.state["layers"][group.name],
                ctx_state["layers"][group.name],
            )
        new_state = {
            "pos": self.state["pos"].at[slot].set(ctx_state["pos"][0]),
            "layers": new_layers,
        }
        if "pred" in self.state:
            new_state["pred"] = self.state["pred"]
        # the eager slot writes leave their outputs on whatever sharding
        # the op picks (a seq-sharded KV cache comes back replicated over
        # "model"): re-commit, or the next decode step compiles anew
        self.state = self._committed(new_state, self.xp)
        self.cur_token = self._committed_token(
            self.cur_token.at[slot, 0].set(first_token)
        )
        self.slot_req[slot] = req_id

    def decode_step(self, params):
        out = self.step(params, {"token": self.cur_token}, self.state)
        self.state = out["state"]
        self.cur_token = out["next_token"]
        if "pred_stats" in out:
            # [predicted, spec_hit, cache_hit, miss, evicted] expert rows
            # this step, summed over layers and ranks (psum'd in-step)
            self.last_pred_stats = np.asarray(out["pred_stats"])
        # per-kind fault counters + per-peer detected tail (only emitted
        # by validated plans whose layers run the demand/predictive path)
        self.last_fault_stats = (
            np.asarray(out["fault_stats"]) if "fault_stats" in out else None
        )
        return np.asarray(out["next_token"][:, 0])

    def release(self, slot: int):
        self.slot_req[slot] = None

    def snapshot_slot(self, slot: int) -> dict:
        """Host-side copy of one slot's decode state in the ctx-transfer
        layout (batch dim 1), re-admittable verbatim via :meth:`admit` —
        the serving layer's evict-to-queue hook. ``token`` is the slot's
        pending input token (the last one it emitted). The shared
        predictive state ("pred") is per-RANK, not per-slot, and is
        deliberately not captured: eviction must not disturb the
        predictor/cache the other slots are hitting."""
        layers = {}
        for group in self.model.plan:
            stacked = group.scan and group.n_cycles > 1
            bax = 1 if stacked else 0

            def read(src, bax=bax):
                idx = (slice(None),) * bax + (slice(slot, slot + 1),)
                return np.asarray(src[idx])

            layers[group.name] = jax.tree.map(
                read, self.state["layers"][group.name]
            )
        return {
            "pos": np.asarray(self.state["pos"][slot:slot + 1]),
            "layers": layers,
            "token": int(np.asarray(self.cur_token[slot, 0])),
            "plan": self.restore_plan(),
        }

    def _subgroup_positions(self) -> np.ndarray:
        """Each flat rank's position within its expert-gather subgroup
        (the ``axis_index % subgroup_size`` the mirrored predictor
        indexes by), in the per-rank state-dim order."""
        sizes = self._mesh_sizes
        n = int(np.prod(list(sizes.values())))
        rem, coords = np.arange(n), {}
        for ax in reversed(list(sizes)):
            coords[ax] = rem % sizes[ax]
            rem = rem // sizes[ax]
        idx = np.zeros(n, np.int64)
        for ax in self.model.geom.expert_axes:
            idx = idx * sizes[ax] + coords[ax]
        return idx % self.model.geom.moe_placement.subgroup_size

    def routed_bitmaps(self, group: Optional[str] = None):
        """The LAST decode step's per-rank routed-expert bitmaps,
        ``(n_ranks, num_experts)`` bool, read from the predictive
        state's ``prev`` leaf (the serving trace-capture hook; None when
        the installed plan runs no predictive/sync-free layers).

        ``group`` picks the layer group (first predictive group in plan
        order by default); scan-stacked groups report their first cycle
        (one layer's routing — the shape the trace tooling consumes).
        Sync-free plans carry the mirrored per-subgroup-position view;
        each rank's OWN row is selected by its subgroup position."""
        pred = self.state.get("pred")
        if not pred:
            return None
        if group is None:
            group = next(
                g.name for g in self.model.plan if g.name in pred
            )
        gdict = pred[group]
        st = gdict[sorted(gdict)[0]]
        gobj = next(g for g in self.model.plan if g.name == group)
        prev = np.asarray(st.prev)
        if gobj.scan and gobj.n_cycles > 1:
            prev = prev[0]
        if prev.ndim == 3:  # mirrored: (n_ranks, G', e_pad) -> own row
            n_ranks = prev.shape[0]
            pos = self._subgroup_positions()
            prev = prev[np.arange(n_ranks), pos]
        return prev[:, : self.model.cfg.moe.num_experts].astype(bool)


class DisaggregatedEngine:
    """Queues + rate matching between context and generation servers."""

    def __init__(self, params, ctx: ContextServer, gen: GenerationServer,
                 health: Optional[HealthMonitor] = None,
                 scheduler: Optional[OnlinePolicyScheduler] = None):
        self.params = params
        self.ctx = ctx
        self.gen = gen
        self.health = health
        self.scheduler = scheduler
        self.queue: list[Request] = []
        self.records: dict[int, RequestRecord] = {}
        self.outputs: dict[int, list[int]] = {}
        self.metrics = ServingMetrics(num_gpus=1)
        self.t = 0.0

    def warmup(self) -> int:
        """Pre-compile the serving variants OFF the serving path: the
        prefill step plus every decode-policy variant the online
        scheduler can switch to (its bucket tables x budget rungs).
        After this, request traffic — including every scheduler switch
        and budget resize — runs with zero recompiles
        (``gen.variants.compiles()`` stays flat). Returns the number of
        decode variants compiled."""
        self.ctx.warmup(self.params)
        tables = (
            self.scheduler.candidate_tables(self.gen)
            if self.scheduler is not None else ()
        )
        return self.gen.warmup(self.params, tables)

    def submit(self, req: Request):
        # engine-shape validation (the Request itself checked basic
        # well-formedness at construction)
        buckets = getattr(self.ctx, "prefill_lens",
                          (self.ctx.prefill_len,))
        if len(req.tokens) not in buckets:
            raise ValueError(
                f"Request {req.req_id}: prompt length {len(req.tokens)} "
                f"matches no context-server bucket (prefill_lens="
                f"{buckets})"
            )
        if len(req.tokens) + req.target_len - 1 > self.gen.cache_len:
            raise ValueError(
                f"Request {req.req_id}: prompt ({len(req.tokens)}) + "
                f"output ({req.target_len}) tokens exceed the decode ring "
                f"capacity cache_len={self.gen.cache_len}"
            )
        self.queue.append(req)
        self.records[req.req_id] = RequestRecord(
            req_id=req.req_id,
            arrival=self.t,
            prompt_len=len(req.tokens),
            target_len=req.target_len,
        )
        self.outputs[req.req_id] = []

    def run(self, steps: int) -> ServingMetrics:
        """Drive the engine: each step = one decode iteration; free slots
        pull queued requests through the context server first."""
        for _ in range(steps):
            for slot in self.gen.free_slots():
                if not self.queue:
                    break
                req = self.queue.pop(0)
                first, state = self.ctx.prefill(self.params, req.tokens)
                rec = self.records[req.req_id]
                rec.first_token_time = self.t
                rec.tokens_out = 1
                rec.add_gather_share(self.ctx.gather_bytes)
                self.outputs[req.req_id].append(first)
                self.gen.admit(slot, req.req_id, first, state)
                self.gen.slot_remaining[slot] = req.target_len - 1
            if self.scheduler is not None:
                # re-resolve BEFORE the step so the bucket matches the
                # slots about to decode; drift input (last_pred_stats)
                # is the previous step's measured split
                moved = self.scheduler.step(
                    self.gen,
                    sum(r is not None for r in self.gen.slot_req),
                )
                if moved:
                    self.metrics.record_transition(
                        int(self.t), moved, self.gen.level,
                        self.gen.fetch_label,
                    )
            toks = self.gen.decode_step(self.params)
            self.t += 1.0
            from repro.core.faults import FAULT_STAT_BASE

            fs = self.gen.last_fault_stats
            if fs is not None:
                self.metrics.record_fault_stats(fs)
            if self.health is not None:
                if fs is not None:
                    tail = fs[FAULT_STAT_BASE:]
                elif self.health.ema.size:
                    # bottom-of-ladder ("all") plans run no per-peer
                    # payload rounds, so there is no fault signal — feed
                    # a clean observation so the EMAs decay and recovery
                    # can re-promote
                    tail = np.zeros_like(self.health.ema)
                else:
                    tail = None
                move = (
                    self.health.observe(tail) if tail is not None else None
                )
                if move == "demote":
                    # fail-silent demotions cap at the all-gather floor:
                    # the terminal "reshard" rung is reserved for the
                    # fail-stop (rank-death) path
                    if self.gen.set_level(
                        min(self.gen.level + 1,
                            self.gen.max_silent_level),
                        bad_peers=self.health.bad_peers(),
                    ):
                        self.metrics.record_transition(
                            int(self.t), "demote", self.gen.level,
                            self.gen.fetch_label,
                        )
                elif move == "promote" and self.gen.level > 0:
                    if self.gen.set_level(
                        self.gen.level - 1,
                        bad_peers=self.health.bad_peers(),
                    ):
                        self.metrics.record_transition(
                            int(self.t), "promote", self.gen.level,
                            self.gen.fetch_label,
                        )
            active = [r for r in self.gen.slot_req if r is not None]
            for slot, rid in enumerate(self.gen.slot_req):
                if rid is None:
                    continue
                rec = self.records[rid]
                # the decode step's gather traffic is shared by its
                # active slots: attribute each request its share
                rec.add_gather_share(
                    self.gen.gather_bytes, 1.0 / len(active)
                )
                if self.gen.last_pred_stats is not None and active:
                    # measured predictive counters (rows -> bytes), the
                    # step's share split over its active slots
                    rec.add_predict_share(
                        self.gen.last_pred_stats, self.gen.expert_bytes,
                        1.0 / len(active),
                    )
                self.outputs[rid].append(int(toks[slot]))
                rec.tokens_out += 1
                self.gen.slot_remaining[slot] -= 1
                if self.gen.slot_remaining[slot] <= 0:
                    rec.done_time = self.t
                    self.metrics.records.append(rec)
                    self.gen.release(slot)
        return self.metrics
