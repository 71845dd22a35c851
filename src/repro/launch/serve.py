"""Serving driver: disaggregated context/generation demo on live arrays.

``python -m repro.launch.serve --arch yi-9b --requests 8`` runs the full
stack at reduced scale: DWDP context server (prefill + KV capture), slot
based continuous-batching generation server, and reports TPS/TTFT.

Gather policies are configured per weight family (the GatherPolicy API):

    --policy moe_experts=split:demand:ring_sliced \
    --policy attn_qkv=merged:all:allgather        \
    --policy dense_ffn=split:all:ring

or ``--policy-file policies.json`` (the ``PolicyTable.to_dict`` JSON
shape, ``{"family_or_default": "layout[:fetch[:transport...]]"}``), or
``--policy auto`` for the roofline-guided resolver. Expert fetch modes:
``all`` (every remote expert every layer), ``demand``
(route-before-gather), ``predictive`` (speculative layer-ahead round
+ cross-step residency cache — ``--cache-budget`` rows per layer; auto
picks it at decode shapes where the overlap pays) and ``sync_free``
(mirrored-predictor decode: the speculative round ships zero index
metadata; see docs/syncfree.md). The pre-PolicyTable flags
(``--weight-layout`` / ``--expert-fetch`` / ``--demand-budget`` /
``--cache-budget``) keep working as the uniform-table spelling and may
not be combined with ``--policy``.

Fault tolerance (docs/robustness.md): ``--fault-spec`` injects
deterministic peer faults into the fetch rounds (outputs stay
bitwise-exact through the checksum-repair path), ``--validate-fetch``
turns on validation without injection, and the ``--health-*`` knobs
tune the HealthMonitor that walks the gather policy down the
sync_free/predictive -> per-peer exclusion -> demand -> all-gather
ladder under persistent peer badness (and back up on recovery).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch, reduced_variant
from repro.core.strategy import PolicyTable
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import build_model
from repro.runtime.engine import (
    ContextServer,
    DisaggregatedEngine,
    GenerationServer,
    HealthMonitor,
    OnlinePolicyScheduler,
    Request,
)


def parse_policy_flags(flags, policy_file=None):
    """``--policy`` / ``--policy-file`` -> a PolicyTable, ``"auto"``,
    ``"auto-online"``, or None (nothing given). Each ``--policy`` value
    is either a standalone literal (``auto`` — roofline-resolved once at
    boot; ``auto-online`` — additionally re-resolved online between
    pre-compiled variants) or ``family=layout[:fetch[:transport
    [:num_slices[:budget]]]]``; the file is the PolicyTable JSON dict.
    Flags override file entries for the same family. Unknown families or
    values raise ``ValueError`` (argparse surfaces them as CLI
    errors)."""
    flags = list(flags or ())
    for lit in ("auto", "auto-online"):
        if lit in flags:
            if len(flags) > 1 or policy_file:
                raise ValueError(
                    f"--policy {lit} stands alone (it resolves every "
                    "family); drop the other --policy/--policy-file "
                    "arguments"
                )
            return lit
    spec: dict = {}
    if policy_file:
        with open(policy_file) as f:
            loaded = json.load(f)
        if not isinstance(loaded, dict):
            raise ValueError(
                f"--policy-file {policy_file!r} must hold a JSON object "
                "mapping families to policy specs"
            )
        spec.update(loaded)
    for flag in flags:
        if "=" not in flag:
            raise ValueError(
                f"--policy expects family=layout[:fetch[:transport...]] "
                f"or the literal 'auto'; got {flag!r}"
            )
        fam, pol = flag.split("=", 1)
        spec[fam] = pol
    if not spec:
        return None
    return PolicyTable.from_dict(spec)


def resolve_cli_policy(args) -> object:
    """Shared CLI resolution for serve-style drivers: parse --policy /
    --policy-file and reject combining them with the explicit uniform
    flags (--weight-layout / --expert-fetch / --demand-budget). Returns
    a PolicyTable, "auto", or None; raises ValueError on conflicts or
    bad specs."""
    legacy_given = [
        name for name, v in (
            ("--weight-layout", args.weight_layout),
            ("--expert-fetch", args.expert_fetch),
            ("--demand-budget", args.demand_budget),
            ("--cache-budget", getattr(args, "cache_budget", None)),
        ) if v is not None
    ]
    policy = parse_policy_flags(args.policy, args.policy_file)
    if policy is not None and legacy_given:
        raise ValueError(
            f"conflicting --policy and uniform flags "
            f"{', '.join(legacy_given)} — pass only --policy"
        )
    return policy


def build_engine(
    cfg,
    *,
    mesh_shape=(1, 1),
    prefill_len: int = 64,
    prefill_buckets: tuple = (),
    cache_len: int = 128,
    max_batch: int = 4,
    ctx_mode: str = "dwdp",
    gen_mode: str = "dep",
    prefetch: str = "allgather",
    weight_layout: str | None = None,
    capacity_from: str = "local",
    expert_fetch: str = "all",
    demand_budget: int = 0,
    cache_budget: int = 0,
    policy=None,
    dtype=jnp.float32,
    seed: int = 0,
    fault_spec=None,
    validate_fetch: bool = False,
    health: "HealthMonitor | None" = None,
    variant_cache_size: int = 16,
    switch_interval: int = 8,
    devices=None,
):
    """A :class:`DisaggregatedEngine` and its model. ``devices`` is the
    explicit device slice the engine's mesh is built on (a serving
    replica's own chips); by default the first ``prod(mesh_shape)``
    visible devices."""
    from repro.launch.mesh import _mesh
    mesh = _mesh(mesh_shape, ("data", "model"), devices=devices)
    sizes = {"data": mesh_shape[0], "model": mesh_shape[1]}
    # seq-sharded KV capture / decode caches split the ring over up to
    # all mesh ranks: round the cache up so every shard degree divides
    n_ranks = max(1, mesh_shape[0] * mesh_shape[1])
    cache_len = -(-cache_len // n_ranks) * n_ranks
    model = build_model(cfg, sizes, dtype=dtype)
    # initialize on the mesh's own first device, then commit each leaf
    # to its sharding, so a replica's weights sit on its own devices
    with jax.default_device(mesh.devices.flat[0]):
        params = model.init_params(jax.random.key(seed))
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), model.param_pspecs(),
        is_leaf=lambda s: isinstance(s, P),
    ))
    ctx = ContextServer(
        model, mesh, sizes, mode=ctx_mode, prefill_len=prefill_len,
        prefill_buckets=prefill_buckets,
        cache_len=cache_len, prefetch=prefetch,
        weight_layout=weight_layout, capacity_from=capacity_from,
        expert_fetch=expert_fetch, demand_budget=demand_budget,
        cache_budget=cache_budget, policy=policy,
        fault_spec=fault_spec, validate_fetch=validate_fetch,
    )
    gen = GenerationServer(
        model, mesh, sizes, mode=gen_mode, max_batch=max_batch,
        cache_len=cache_len,
        weight_layout=weight_layout, capacity_from=capacity_from,
        expert_fetch=expert_fetch, demand_budget=demand_budget,
        cache_budget=cache_budget, policy=policy,
        fault_spec=fault_spec, validate_fetch=validate_fetch,
        variant_cache_size=variant_cache_size,
    )
    scheduler = None
    if policy == "auto-online":
        scheduler = OnlinePolicyScheduler(
            model, sizes, gen._shape, interval=switch_interval,
        )
    return DisaggregatedEngine(
        params, ctx, gen, health=health, scheduler=scheduler
    ), model


def run_serving(args, cfg, policy):
    """The --serving path: N live replicas (same weights, independent
    clocks) behind the least-loaded router, continuous-batching rolling
    admission, optional SLO gate. Prints the percentile summary
    (TTFT/TPOT p50/p95/p99) and the admission counters."""
    from repro.runtime.serving import (
        AdmissionController,
        LiveReplicaClient,
        MultiReplicaEngine,
        ServingScheduler,
        SLOConfig,
        WorkloadConfig,
        synthesize_workload,
    )

    if args.isl_buckets:
        buckets = tuple(
            sorted({int(b) for b in args.isl_buckets.split(",")})
        )
    else:
        buckets = (args.prefill_len,)
    slo = SLOConfig(
        target_tps_user=args.slo_tps_user,
        ttft_budget_s=args.slo_ttft,
        max_queue=args.max_queue,
    )
    gated = args.slo_tps_user or args.slo_ttft or args.max_queue
    # every replica runs on mesh (1, 1) of its own devices: replica i
    # holds devices [i*n, (i+1)*n)
    mesh_shape = (1, 1)
    n = mesh_shape[0] * mesh_shape[1]
    devices = jax.devices()
    if args.replicas * n > len(devices):
        raise ValueError(
            f"--replicas {args.replicas} needs {args.replicas * n} "
            f"devices; only {len(devices)} visible"
        )
    schedulers = []
    for i in range(args.replicas):
        engine, _ = build_engine(
            cfg,
            mesh_shape=mesh_shape,
            devices=devices[i * n:(i + 1) * n],
            prefill_len=max(buckets),
            prefill_buckets=buckets,
            cache_len=max(buckets) + args.output_len,
            max_batch=args.max_batch,
            ctx_mode=args.ctx_mode,
            gen_mode=args.gen_mode,
            weight_layout=args.weight_layout,
            capacity_from=args.capacity_from,
            expert_fetch=args.expert_fetch or "all",
            demand_budget=args.demand_budget or 0,
            cache_budget=args.cache_budget or 0,
            policy=policy,
            variant_cache_size=args.variant_cache_size,
            switch_interval=args.switch_interval,
        )
        client = LiveReplicaClient.from_engine(engine)
        if not args.no_warmup:
            client.warmup()
        admission = (
            AdmissionController(slo, client.step_time) if gated else None
        )
        schedulers.append(ServingScheduler(client, admission=admission))
    if not args.no_warmup:
        print(f"warmup: {args.replicas} replica(s), prefill buckets "
              f"{list(buckets)} pre-compiled")
    fleet = MultiReplicaEngine(schedulers)
    wl = WorkloadConfig(
        num_requests=args.requests,
        isl_buckets=buckets,
        osl=args.output_len,
        arrival_rate=args.arrival_rate,
    )
    fleet.submit(synthesize_workload(wl, vocab_size=cfg.vocab_size))
    metrics = fleet.run()
    s = metrics.summary(horizon=fleet.horizon())
    print("serving summary:", s)
    print("ttft p50/p95/p99:",
          s["ttft_p50_s"], s["ttft_p95_s"], s["ttft_p99_s"])
    print("tpot p50/p95/p99:",
          s["tpot_p50_s"], s["tpot_p95_s"], s["tpot_p99_s"])
    print("recovery:", {k: s[k] for k in (
        "rank_deaths", "migrated", "requeued",
        "time_to_recover_p50_s", "time_to_recover_p95_s")})
    for i, sched in enumerate(schedulers):
        n = sum(1 for r in fleet.assignments.values() if r == i)
        print(f"replica {i}: {n} request(s), {sched.steps} decode "
              f"step(s), horizon {sched.t:.3f}s")
    for rid, toks in list(schedulers[0].outputs.items())[:4]:
        print(f"req {rid}: {toks[:10]}{'...' if len(toks) > 10 else ''}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prefill-len", type=int, default=64)
    ap.add_argument("--output-len", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--ctx-mode", default="dwdp")
    ap.add_argument("--policy", action="append", default=None,
                    metavar="FAMILY=SPEC",
                    help="per-family gather policy (repeatable): "
                         "family=layout[:fetch[:transport[:num_slices"
                         "[:budget]]]] with families moe_experts, "
                         "attn_qkv, attn_out, dense_ffn, default — or "
                         "the literal 'auto' for the roofline-guided "
                         "resolver, or 'auto-online' to additionally "
                         "re-resolve online (phase/batch buckets + "
                         "measured hit-rate drift) switching between "
                         "pre-compiled forward variants with zero "
                         "recompiles (docs/policy_switching.md)")
    ap.add_argument("--policy-file", default=None,
                    help="JSON file mapping families to policy specs "
                         "(PolicyTable.to_dict shape); --policy flags "
                         "override file entries")
    ap.add_argument("--weight-layout", default=None,
                    choices=["merged", "split"],
                    help="uniform gathered-weight representation for "
                         "every DWDP family (the pre-PolicyTable "
                         "spelling of --policy default=LAYOUT)")
    ap.add_argument("--capacity-from", default="local",
                    choices=["local", "global"],
                    help="MoE capacity derivation: local shard count or "
                         "layout-invariant per-row global shape")
    ap.add_argument("--gen-mode", default="dep", choices=["dep", "dwdp"],
                    help="generation-server strategy (dwdp shards the "
                         "weights and gathers per layer — the mode the "
                         "on-demand expert fetch accelerates)")
    ap.add_argument("--expert-fetch", default=None,
                    choices=["all", "demand", "predictive", "sync_free"],
                    help="uniform MoE expert-gather selection (the "
                         "pre-PolicyTable spelling of --policy "
                         "moe_experts=split:FETCH); 'predictive' adds "
                         "the layer-ahead speculative round + cross-step "
                         "residency cache at decode; 'sync_free' mirrors "
                         "the predictor on every rank so the speculative "
                         "round carries zero index metadata")
    ap.add_argument("--demand-budget", type=int, default=None,
                    help="per-peer demand-fetch row budget (0 = auto: 2x "
                         "the expected distinct-expert coverage; for "
                         "predictive, the speculative/correction rounds)")
    ap.add_argument("--cache-budget", type=int, default=None,
                    help="expert rows of the predictive fetch's "
                         "cross-step residency cache per layer (0 = "
                         "cache off; --policy auto sizes it from HBM "
                         "headroom)")
    ap.add_argument("--fault-spec", default=None,
                    metavar="SPEC",
                    help="inject deterministic fetch faults, e.g. "
                         "'seed=3,drop=0.1,corrupt=0.05,peers=2|5' "
                         "(keys: seed/drop/zero/corrupt/cache/peers). "
                         "Implies payload validation; outputs stay "
                         "bitwise-exact via the repair path")
    ap.add_argument("--validate-fetch", action="store_true",
                    help="checksum-validate fetched expert rows without "
                         "injecting faults (the production hardening "
                         "switch; implied by --fault-spec)")
    ap.add_argument("--health-decay", type=float, default=0.7,
                    help="HealthMonitor per-peer fault-event EMA decay")
    ap.add_argument("--health-demote", type=float, default=0.5,
                    help="per-peer EMA above which the policy ladder "
                         "demotes (predictive -> demand -> all)")
    ap.add_argument("--health-promote", type=float, default=0.1,
                    help="all-peer EMA below which the ladder re-promotes")
    ap.add_argument("--health-dwell", type=int, default=2,
                    help="min decode steps between ladder transitions")
    ap.add_argument("--no-health", action="store_true",
                    help="disable the HealthMonitor even when validating")
    ap.add_argument("--variant-cache-size", type=int, default=16,
                    help="max pre-compiled forward variants the "
                         "generation server retains (policy tables x "
                         "exclusion sets, LRU)")
    ap.add_argument("--switch-interval", type=int, default=8,
                    help="decode steps between auto-online drift "
                         "re-resolutions (bucket boundaries re-resolve "
                         "immediately)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip pre-compiling the scheduler's candidate "
                         "variants before serving (first switches then "
                         "pay a trace+compile on the serving path)")
    ap.add_argument("--full", action="store_true",
                    help="use the full config (default: reduced smoke)")
    serving = ap.add_argument_group(
        "serving", "continuous-batching serving path (docs/serving.md): "
        "rolling admission into decode slots as they free, SLO-aware "
        "admission control, N independent data-parallel replicas behind "
        "the least-loaded router"
    )
    serving.add_argument("--serving", action="store_true",
                         help="serve through ServingScheduler / "
                              "MultiReplicaEngine instead of the "
                              "fixed-slot engine loop")
    serving.add_argument("--replicas", type=int, default=1,
                         help="independent engine replicas (no "
                              "cross-replica synchronization; the "
                              "router balances by backlog)")
    serving.add_argument("--isl-buckets", default=None,
                         metavar="L1,L2,...",
                         help="prompt-length mix for the synthesized "
                              "workload (each a pow2 prefill bucket, "
                              "pre-compiled at warmup; default: one "
                              "bucket of --prefill-len)")
    serving.add_argument("--arrival-rate", type=float, default=0.0,
                         help="Poisson arrival rate, requests/s of "
                              "simulated queue time (0 = all requests "
                              "queued at t=0)")
    serving.add_argument("--slo-tps-user", type=float, default=0.0,
                         help="per-user decode-rate floor: admissions "
                              "projected below it queue; sustained "
                              "violation evicts-to-queue (0 = off)")
    serving.add_argument("--slo-ttft", type=float, default=0.0,
                         help="TTFT budget in seconds: queued requests "
                              "whose wait alone exceeds it are shed "
                              "(0 = off)")
    serving.add_argument("--max-queue", type=int, default=0,
                         help="queued requests beyond which arrivals "
                              "are shed (0 = unbounded)")
    args = ap.parse_args(argv)
    try:
        policy = resolve_cli_policy(args)
    except ValueError as e:
        ap.error(str(e))
    enable_compile_cache()
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduced_variant(cfg)
    if args.serving:
        return run_serving(args, cfg, policy)
    health = None
    if (args.fault_spec or args.validate_fetch) and not args.no_health:
        health = HealthMonitor(
            decay=args.health_decay,
            demote_threshold=args.health_demote,
            promote_threshold=args.health_promote,
            min_dwell=args.health_dwell,
        )
    engine, model = build_engine(
        cfg,
        prefill_len=args.prefill_len,
        cache_len=args.prefill_len + args.output_len,
        max_batch=args.max_batch,
        ctx_mode=args.ctx_mode,
        gen_mode=args.gen_mode,
        weight_layout=args.weight_layout,
        capacity_from=args.capacity_from,
        expert_fetch=args.expert_fetch or "all",
        demand_budget=args.demand_budget or 0,
        cache_budget=args.cache_budget or 0,
        policy=policy,
        fault_spec=args.fault_spec,
        validate_fetch=args.validate_fetch,
        health=health,
        variant_cache_size=args.variant_cache_size,
        switch_interval=args.switch_interval,
    )
    if not args.no_warmup:
        n = engine.warmup()
        print(f"warmup: {n} decode variant(s) pre-compiled")
    print("ctx policies:", engine.ctx.xp.policies.describe())
    print("gen policies:", engine.gen.xp.policies.describe())
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        engine.submit(
            Request(
                req_id=i,
                tokens=rng.integers(
                    0, cfg.vocab_size, args.prefill_len
                ).astype(np.int32),
                target_len=args.output_len,
            )
        )
    steps = args.output_len * (args.requests // args.max_batch + 2)
    metrics = engine.run(steps)
    print("summary:", metrics.summary(horizon=float(steps)))
    if engine.gen.level or metrics.policy_transitions:
        print(
            f"ladder level: {engine.gen.level} ({engine.gen.fetch_label})"
        )
    if engine.scheduler is not None:
        print(
            "variant cache:", dict(engine.gen.variants.stats),
            f"entries={len(engine.gen.variants)}",
            f"signatures={engine.gen.variants.compiles()}",
        )
    for rid, toks in list(engine.outputs.items())[:4]:
        print(f"req {rid}: {toks[:10]}{'...' if len(toks) > 10 else ''}")


if __name__ == "__main__":
    main()
