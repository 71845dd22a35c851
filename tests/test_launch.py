"""Launch-layer tests: the bf16 engine, the single-rank MoE kernel path,
replica device placement and the compile-cache location.

Everything runs on the CPU at the reduced DeepSeek-R1 size; the replica
placement test runs in a subprocess with 4 fake host devices.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
CHECKOUT = Path(__file__).resolve().parents[1]


def _r1_smoke():
    from repro.configs import get_arch, reduced_variant

    return reduced_variant(get_arch("deepseek-r1"))


def _engine(cfg, **kw):
    from repro.launch.serve import build_engine

    engine, _ = build_engine(
        cfg, prefill_len=16, cache_len=32, max_batch=2, **kw
    )
    return engine


def _prompts(cfg, n=2):
    rng = np.random.default_rng(3)
    return [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
            for _ in range(n)]


def test_bf16_engine_serves_and_tracks_f32():
    """The bf16 engine (the dtype a TPU run uses) serves two requests,
    and its prefill logits stay within bf16 rounding of the f32
    engine's. bf16 keeps 8 mantissa bits: the weights and every layer's
    activations round at 2^-9 relative, so over two layers and the head
    the logits may move by a few percent of their scale; 5e-2 allows
    that, while a wrong dtype path (fp8 weights, or a dropped layer)
    moves them by far more."""
    from repro.runtime.engine import Request

    cfg = _r1_smoke()
    bf16 = _engine(cfg, dtype=jnp.bfloat16)
    f32 = _engine(cfg, dtype=jnp.float32)
    leaves = jax.tree.leaves(bf16.params)
    assert all(x.dtype == jnp.bfloat16 for x in leaves
               if jnp.issubdtype(x.dtype, jnp.floating))
    prompts = _prompts(cfg)
    for i, p in enumerate(prompts):
        bf16.submit(Request(req_id=i, tokens=p, target_len=4))
    bf16.run(steps=12)
    assert sorted(bf16.outputs) == [0, 1]
    assert all(len(t) == 4 for t in bf16.outputs.values())
    v = cfg.vocab_size
    for p in prompts:
        lo = np.asarray(
            bf16.ctx.prefill_logits(bf16.params, p)[0, :v], np.float32
        )
        hi = np.asarray(f32.ctx.prefill_logits(f32.params, p)[0, :v])
        assert np.all(np.isfinite(lo))
        rel = np.max(np.abs(lo - hi)) / np.max(np.abs(hi))
        assert rel <= 5e-2, rel


def test_single_rank_moe_kernel_matches_jnp(monkeypatch):
    """On one rank every expert is resident; on a TPU the MoE layer runs
    the split SwiGLU kernel with an empty remote bank instead of the jnp
    grouped FFN. Forcing that choice here (interpret mode) must give the
    jnp path's prefill logits: the same f32 math, summed in another
    order, so agreement to 1e-5 of the logit scale."""
    import repro.kernels.split_gemm as lib

    cfg = _r1_smoke()
    prompt = _prompts(cfg, 1)[0]
    ref = _engine(cfg)
    want = np.asarray(ref.ctx.prefill_logits(ref.params, prompt))
    seen = []
    kernel = lib.split_swiglu

    def recorded(*args, **kw):
        seen.append(kw.get("impl"))
        return kernel(*args, **kw)

    monkeypatch.setattr(lib, "default_dense_impl",
                        lambda phase: "jnp" if phase == "train" else "pallas")
    monkeypatch.setattr(lib, "split_swiglu", recorded)
    eng = _engine(cfg)
    got = np.asarray(eng.ctx.prefill_logits(eng.params, prompt))
    assert seen and set(seen) == {"pallas"}
    v = cfg.vocab_size
    rel = np.max(np.abs(got[:, :v] - want[:, :v])) / np.max(np.abs(want[:, :v]))
    assert rel <= 1e-5, rel


REPLICA_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import repro.launch.serve as serve

placed = []
build = serve.build_engine

def recording(*args, **kw):
    engine, model = build(*args, **kw)
    placed.append(sorted({d.id for x in jax.tree.leaves(engine.params)
                          for d in x.devices()}))
    return engine, model

serve.build_engine = recording
argv = ["--arch", "deepseek-r1", "--serving", "--requests", "2",
        "--prefill-len", "16", "--output-len", "2", "--max-batch", "2",
        "--no-warmup"]
serve.main(argv + ["--replicas", "2"])
try:
    serve.main(argv + ["--replicas", "5"])
    too_many = None
except ValueError as e:
    too_many = str(e)
print("RESULT::" + json.dumps({"placed": placed, "too_many": too_many}))
"""


def test_replicas_on_their_own_devices(tmp_path):
    """``--serving --replicas N`` puts replica i's weights on device i
    (mesh (1, 1) each) and refuses more replicas than devices."""
    env = dict(os.environ, PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, "-c", REPLICA_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT::")][-1]
    r = json.loads(line[len("RESULT::"):])
    assert r["placed"] == [[0], [1]], r
    assert r["too_many"] and "needs 5 devices" in r["too_many"], r


def test_compile_cache_in_checkout_when_unset(monkeypatch):
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing
    assert jax.config.jax_compilation_cache_dir == before
