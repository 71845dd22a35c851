"""Smoke run of the DWDP serving path on TPU at DeepSeek-R1's published widths.

    python chip_smoke.py             # one chip: the serving path
    python chip_smoke.py --chips 4   # four chips: DWDP on mesh (1, 4) against one chip

It needs a TPU. With none it says so on stderr and exits 1: there is no
fallback to the CPU. Every check raises when it fails, so the script then
exits non-zero. Only when all of them pass is the last line of standard
output one JSON object: ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.

Configuration: ``configs/deepseek_r1.py`` at its published widths
(d_model 7168, 128 heads x head_dim 128, 8 KV heads as the repo's GQA
stand-in for MLA, dense d_ff 18432, expert d_ff 2048, shared expert d_ff
2048, top-8 routing, vocabulary 129,280), in bf16, with random weights
from ``--seed``. Cuts from the published model:

- ``num_layers=3`` (published 61) and ``first_dense=1`` (published 3):
  one dense layer, then a scanned MoE group of two cycles;
- ``num_experts=16`` (published 256). The router's width drops with it,
  so top-8 picks among 16 experts. An expert layer that holds a share of
  the 256 experts and routes over all of them is later work.

One chip: ``launch.serve.build_engine`` (ctx ``dwdp``, gen ``dep``, mesh
(1, 1)), then the ``--serving`` path: ``LiveReplicaClient.warmup``,
``ServingScheduler`` and ``MultiReplicaEngine``. Eight requests with
prompts from the buckets {1024, 8192} (8192 is the paper's input
length), 32 output tokens each, 8 decode slots. It prints the compile
and warm serve seconds (host clock, the window closed by
``block_until_ready``), tokens out, peak device memory, the executable
counts before and after the served window (which must be equal), the
Pallas calls of each lowered step against its ``tpu_custom_call`` count,
and each kernel that ran against its ``ref.py`` twin at the served
shapes.

Four chips: the same configuration and seed served first on device 0
(mesh (1, 1)), then on mesh (1, 4) with ctx ``dwdp`` (all-fetch) and gen
``dwdp`` with ``expert_fetch="sync_free"``. The prefill logits of both
buckets must agree within ``LOGIT_TOL``, the greedy streams must agree
or part only at a near-tie, whose margin is printed. The DWDP engine
must have run the split SwiGLU with a remote bank and the demand SwiGLU:
neither runs on one chip, so this comparison is their check end to end.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BUCKETS = (1024, 8192)
OUTPUT_LEN = 32
REQUESTS = 8
MAX_BATCH = 8
# Four-chip phase: decode slots of the DWDP engine. Decode keeps its
# batch off the "model" axis, so every rank routes every row, and the
# demand (route-before-gather) kernel engages only while rows x top-8 <
# the 12 experts remote to a rank: at one row.
MAX_BATCH_4 = 1

# Kernel against its reference: max|kernel - ref| / max|ref|. The kernel
# rounds its output, and the SwiGLU kernels their hidden activation, to
# bf16 (2^-9 relative each); the reference is f32 at "highest" matmul
# precision on the same bf16 inputs. 2e-2 is five bf16 ulps of the
# output's scale; weights or activations rounded to fp8 (2^-4) fail it.
KERNEL_TOL = 2e-2
# One chip against four: max|logits_4 - logits_1| / max|logits_1| over
# the prefill's last-position logits. Both runs are bf16, but the DWDP
# engine splits attention heads and the dense FFN over four ranks and
# sums their bf16 partials in another order, so every layer's output
# rounds differently: a few bf16 ulps per layer, over three layers and
# the head.
LOGIT_TOL = 5e-2
# A greedy stream may part from the other only at a near-tie: where the
# two parting tokens' logits lie closer than twice the largest logit
# difference the two engines showed on that prompt's prefill (each
# engine's logit may have moved by that much, in opposite directions).
NEAR_TIE = 2.0

SPLIT_OPS = (
    "split_swiglu", "split_swiglu_demand", "split_dense_ffn",
    "split_stack_matmul", "split_reduce_matmul",
)


def smoke_config():
    """DeepSeek-R1 at its published widths, cut in depth and experts."""
    from repro.configs import get_arch

    base = get_arch("deepseek-r1")
    return dataclasses.replace(
        base,
        name="deepseek-r1-L3-E16",
        num_layers=3,
        moe=dataclasses.replace(base.moe, num_experts=16, first_dense=1),
    )


def describe(cfg) -> str:
    m = cfg.moe
    return (
        f"config {cfg.name}: d_model {cfg.d_model}, heads {cfg.num_heads} x "
        f"{cfg.head_dim} (kv {cfg.num_kv_heads}), dense d_ff {cfg.d_ff}, "
        f"experts {m.num_experts} x d_ff {m.d_ff} top-{m.top_k}, shared "
        f"d_ff {m.shared_d_ff}, vocab {cfg.vocab_size}; cuts: num_layers "
        f"61 -> {cfg.num_layers}, first_dense 3 -> {m.first_dense}, "
        f"num_experts 256 -> {m.num_experts}; bf16, random weights"
    )


@contextlib.contextmanager
def record_kernels():
    """Record every split-bank kernel call traced inside the block, as
    ``{(op name, ((shape, dtype), ...)): calls}``. Calls that take the
    jnp formulation (``impl="jnp"``) run no kernel and are not recorded."""
    import repro.kernels.split_gemm as lib

    seen: dict = {}
    originals = {name: getattr(lib, name) for name in SPLIT_OPS}

    def wrap(name, fn):
        def recorded(*args, impl=None, **kw):
            if impl in (None, "pallas"):
                sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
                seen[(name, sig)] = seen.get((name, sig), 0) + 1
            return fn(*args, impl=impl, **kw)
        return recorded

    for name, fn in originals.items():
        setattr(lib, name, wrap(name, fn))
    try:
        yield seen
    finally:
        for name, fn in originals.items():
            setattr(lib, name, fn)


def _pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation in a jaxpr and its sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_pallas_calls(inner))
    return found


def check_lowered(label: str, step, *args) -> None:
    """Every Pallas call the step traces must lower to a Mosaic
    ``tpu_custom_call`` (an interpret-mode kernel lowers to none)."""
    traced = step.trace(*args)
    calls = _pallas_calls(traced.jaxpr.jaxpr)
    text = traced.lower().as_text()
    n_custom = text.count("tpu_custom_call")
    print(f"lowered {label}: {len(calls)} pallas_call, {n_custom} "
          f"tpu_custom_call")
    if n_custom != len(calls):
        raise AssertionError(
            f"{label}: {len(calls)} Pallas calls but {n_custom} "
            "tpu_custom_call in the lowered step"
        )


def _random_args(key, sig):
    """Seeded inputs of one kernel signature: activations at unit scale,
    weights at fan-in^-1/2 as in init."""
    import jax
    import jax.numpy as jnp

    args = []
    for i, (k, (shape, dtype)) in enumerate(
            zip(jax.random.split(key, len(sig)), sig)):
        scale = shape[-2] ** -0.5 if i else 1.0
        args.append(
            (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
        )
    return args


def _kernel_error(sig, key) -> float:
    """max|kernel - ref| / max|ref| of the split SwiGLU at one served
    signature, on seeded inputs. The f32 reference runs two experts at a
    time, so that it fits next to the kernel's output; every array dies
    with the call."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import split_gemm as lib

    x, *w = _random_args(key, sig)
    got = lib.split_swiglu(x, *w, impl="pallas")
    banks = [jnp.concatenate(pair) for pair in zip(w[:3], w[3:])]
    del w
    err = scale = 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(0, x.shape[0], 2):
            def f32(a, j=slice(i, i + 2)):
                return a[j].astype(jnp.float32)

            want = lib.split_grouped_swiglu_ref(
                f32(x), *map(f32, banks),
                *(b[:0].astype(jnp.float32) for b in banks),
            )
            err = max(err, float(jnp.max(jnp.abs(f32(got) - want))))
            scale = max(scale, float(jnp.max(jnp.abs(want))))
    return err / scale


def check_kernels(seen: dict, seed: int) -> None:
    """Each kernel that ran, at the shapes it was served, against its
    f32 ``ref.py`` twin on seeded random inputs. On one chip that is the
    split SwiGLU of the single-rank MoE layer (every expert local)."""
    import jax

    if not seen:
        raise AssertionError("no split-bank kernel ran on the served path")
    base = jax.random.key(seed)
    for n, ((name, sig), calls) in enumerate(sorted(seen.items())):
        if name != "split_swiglu":
            raise AssertionError(f"no reference check for {name}")
        rel = _kernel_error(sig, jax.random.fold_in(base, n))
        shapes = ", ".join(f"{s}:{d}" for s, d in sig)
        print(f"kernel {name} [{shapes}] x{calls} traced: max|kernel - "
              f"ref| / max|ref| = {rel!r} (tol {KERNEL_TOL})")
        if not rel <= KERNEL_TOL:
            raise AssertionError(f"{name} off its reference: {rel!r}")


def print_memory(device) -> None:
    stats = device.memory_stats() or {}
    print(f"{device}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"of bytes_limit {stats.get('bytes_limit')}")


def executables(engine) -> int:
    return engine.ctx.variants.compiles() + engine.gen.variants.compiles()


def _engine(cfg, *, seed, devices, mesh_shape=(1, 1), max_batch=MAX_BATCH,
            **kw):
    import jax.numpy as jnp
    from repro.launch.serve import build_engine

    engine, _ = build_engine(
        cfg,
        mesh_shape=mesh_shape,
        devices=devices,
        prefill_len=max(BUCKETS),
        prefill_buckets=BUCKETS,
        cache_len=max(BUCKETS) + OUTPUT_LEN,
        max_batch=max_batch,
        ctx_mode="dwdp",
        # capacity from each row's global token count: the same tokens
        # drop on one chip and on four, whatever each rank holds
        capacity_from="global",
        dtype=jnp.bfloat16,
        seed=seed,
        **kw,
    )
    return engine


def one_chip(cfg, seed: int) -> None:
    """The default phase: serve REQUESTS requests on one chip."""
    import jax
    from repro.runtime.serving import (
        LiveReplicaClient,
        MultiReplicaEngine,
        ServingScheduler,
        WorkloadConfig,
        synthesize_workload,
    )

    device = jax.devices()[0]
    print(describe(cfg))
    with record_kernels() as seen:
        t0 = time.perf_counter()
        engine = _engine(cfg, seed=seed, devices=[device], gen_mode="dep")
        jax.block_until_ready(engine.params)
        print(f"build (random init): {time.perf_counter() - t0!r} s")
        client = LiveReplicaClient.from_engine(engine)
        t0 = time.perf_counter()
        client.warmup()
        jax.block_until_ready(engine.gen.state)
        print(f"compile (warmup of {len(BUCKETS)} prefill buckets + "
              f"decode): {time.perf_counter() - t0!r} s")
    before = executables(engine)
    fleet = MultiReplicaEngine([ServingScheduler(client)])
    fleet.submit(synthesize_workload(
        WorkloadConfig(num_requests=REQUESTS, isl_buckets=BUCKETS,
                       osl=OUTPUT_LEN, seed=seed),
        vocab_size=cfg.vocab_size,
    ))
    t0 = time.perf_counter()
    metrics = fleet.run()
    jax.block_until_ready(engine.gen.state)
    serve_s = time.perf_counter() - t0
    after = executables(engine)
    summary = metrics.summary(horizon=fleet.horizon())
    outputs = fleet.schedulers[0].outputs
    tokens = sum(len(t) for t in outputs.values())
    print(f"served: {summary['completed']}/{REQUESTS} requests, {tokens} "
          f"tokens out, warm serve {serve_s!r} s (host clock)")
    print(f"executables before/after the served window: {before} / {after}")
    print_memory(device)
    if summary["completed"] != REQUESTS or tokens != REQUESTS * OUTPUT_LEN:
        raise AssertionError(f"not every request completed: {summary}")
    if after != before:
        raise AssertionError(f"served window compiled: {before} -> {after}")

    params = engine.params
    for length in BUCKETS:
        _, step, _ = engine.ctx.bucket(length)
        check_lowered(f"prefill {length}", step, params,
                      {"tokens": np.zeros((1, length), np.int32)})
    gen = engine.gen
    check_lowered("decode", gen.step, params, {"token": gen.cur_token},
                  gen.state)
    # free the engine's weights and caches: the f32 references need room
    del engine, client, fleet, params, gen
    gc.collect()
    check_kernels(seen, seed)


def _serve_streams(engine, prompts: dict) -> dict:
    """Greedy streams of one request per prompt, through the serving
    path (``LiveReplicaClient`` warmed, ``ServingScheduler``)."""
    from repro.runtime.serving import LiveReplicaClient, ServingScheduler
    from repro.runtime.serving.workload import ServedRequest

    client = LiveReplicaClient.from_engine(engine)
    client.warmup()
    sched = ServingScheduler(client)
    sched.submit([
        ServedRequest(req_id=length, prompt_len=length,
                      target_len=OUTPUT_LEN, tokens=tokens)
        for length, tokens in prompts.items()
    ])
    sched.run()
    return {rid: list(toks) for rid, toks in sched.outputs.items()}


def four_chip(cfg, seed: int) -> None:
    """DWDP on mesh (1, 4) against the same model on one chip."""
    import jax

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices; {len(devices)} found")
    print(describe(cfg))
    vocab = cfg.vocab_size
    rng = np.random.default_rng(seed)
    prompts = {
        length: rng.integers(0, vocab, length).astype(np.int32)
        for length in BUCKETS
    }

    ref = _engine(cfg, seed=seed, devices=devices[:1], gen_mode="dep")
    logits_1 = {
        length: np.asarray(
            ref.ctx.prefill_logits(ref.params, p)[0, :vocab], np.float32
        )
        for length, p in prompts.items()
    }
    streams_1 = _serve_streams(ref, prompts)
    del ref
    gc.collect()

    with record_kernels() as seen:
        t0 = time.perf_counter()
        dwdp = _engine(
            cfg, seed=seed, devices=devices[:4], mesh_shape=(1, 4),
            max_batch=MAX_BATCH_4, gen_mode="dwdp",
            expert_fetch="sync_free",
        )
        logits_4 = {
            length: np.asarray(
                dwdp.ctx.prefill_logits(dwdp.params, p)[0, :vocab],
                np.float32,
            )
            for length, p in prompts.items()
        }
        streams_4 = _serve_streams(dwdp, prompts)
        print(f"dwdp (1, 4) build + compile + serve: "
              f"{time.perf_counter() - t0!r} s")
    print("gen policies:", dwdp.gen.xp.policies.describe())
    for name, sig in sorted(seen):
        print(f"kernel traced on (1, 4): {name} "
              f"{[s for s, _ in sig]}")
    ran = {name for name, _ in seen}
    for need in ("split_swiglu", "split_swiglu_demand"):
        if need not in ran:
            raise AssertionError(f"{need} did not run on mesh (1, 4)")

    spread = {}
    for length in BUCKETS:
        a, b = logits_1[length], logits_4[length]
        spread[length] = float(np.max(np.abs(a - b)))
        rel = spread[length] / float(np.max(np.abs(a)))
        print(f"prefill {length}: max|logits_4 - logits_1| / "
              f"max|logits_1| = {rel!r} (tol {LOGIT_TOL})")
        if not rel <= LOGIT_TOL:
            raise AssertionError(f"prefill {length} logits differ: {rel!r}")

    for length in BUCKETS:
        s1, s4 = streams_1[length], streams_4[length]
        k = next((i for i, (x, y) in enumerate(zip(s1, s4)) if x != y),
                 None)
        if k is None:
            print(f"decode {length}: {len(s1)} greedy tokens agree")
            continue
        context = np.concatenate([prompts[length],
                                  np.asarray(s1[:k], np.int32)])
        logits = np.asarray(
            dwdp.ctx.prefill_logits(dwdp.params, context)[0, :vocab],
            np.float32,
        )
        margin = float(abs(logits[s1[k]] - logits[s4[k]]))
        limit = NEAR_TIE * spread[length]
        print(f"decode {length}: streams agree for {k} tokens, then part "
              f"({s1[k]} vs {s4[k]}) at a logit margin of {margin!r} "
              f"(near-tie below {limit!r}: {NEAR_TIE} x the prefill's "
              f"largest logit difference)")
        if not margin <= limit:
            raise AssertionError(f"decode {length} parts at no near-tie")
    for device in devices[:4]:
        print_memory(device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the DWDP mesh (1, 4) phase and the "
                         "one-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {device.platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    cfg = smoke_config()
    if args.chips == 4:
        four_chip(cfg, args.seed)
    else:
        one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
