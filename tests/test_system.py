"""End-to-end system behaviour: the paper's full story on a real model.

Train a small LM with byzantine workers present under a strong attack and
assert the robust GAR defends while plain averaging fails — Definition 1
made executable — plus attacks/sharding/dryrun plumbing sanity.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RobustConfig
from repro.core import attacks
from repro.data import lm_batches
from repro.dist import init_train_state, make_train_step, split_workers
from repro.dist.sharding import param_specs, sanitize_spec
from repro import models as MD
from repro.optim import sgd, constant

KEY = jax.random.key(0)
CFG = ArchConfig(name="sys-t", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128)


def _train(gar, attack, steps=16, n=11, f=2):
    rcfg = RobustConfig(n_workers=n, f=f, gar=gar)
    params = MD.init_model(KEY, CFG)
    opt = sgd(momentum=0.9)
    state = init_train_state(opt, params)
    step = jax.jit(make_train_step(CFG, rcfg, opt, constant(0.05),
                                   chunk_q=16, attack=attack))
    it = lm_batches(CFG.vocab_size, n * 2, 16, seed=11)
    losses = []
    for i in range(steps):
        b = split_workers(next(it), n)
        params, state, m = step(params, state, b, jax.random.fold_in(KEY, i))
        losses.append(float(m["loss"]))
    return losses


def test_end_to_end_byzantine_defence():
    clean = _train("multi_bulyan", "none")
    attacked = _train("multi_bulyan", "inf")
    broken = _train("average", "inf")
    # robust training converges with or without the attack
    assert clean[-1] < clean[0]
    assert np.isfinite(attacked[-1]) and attacked[-1] < attacked[0] + 0.1
    # averaging under the same attack does not reach the robust loss
    assert (not np.isfinite(broken[-1])) or broken[-1] > attacked[-1] + 0.3


def test_all_attacks_produce_finite_training_with_robust_gar():
    for attack in attacks.ATTACKS:
        losses = _train("multi_bulyan", attack, steps=6)
        assert np.isfinite(losses[-1]), attack


def test_param_specs_cover_every_leaf():
    for name in ("qwen3-moe-30b-a3b", "jamba-1.5-large-398b", "whisper-tiny"):
        from repro.configs import get_config
        cfg = get_config(name).reduced()
        params = MD.init_model(KEY, cfg)
        specs = param_specs(params)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "_normalized_spec") or x.__class__.__name__ == "PartitionSpec")
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert len(tuple(spec)) <= leaf.ndim, (spec, leaf.shape)


def test_sanitize_spec_drops_indivisible():
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    s = sanitize_spec(P(None, "model"), (384, 51865), FakeMesh())
    assert tuple(s) == (None, None)
    s2 = sanitize_spec(P(None, "model"), (384, 51872), FakeMesh())
    assert tuple(s2) == (None, "model")


def test_dryrun_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
      %all-gather.1 = bf16[16,384,4096]{2,1,0} all-gather(%p0), replica_groups={}
      %ar = f32[128]{0} all-reduce(%x), to_apply=%add
      %ag-start = (f32[4], f32[8]) all-gather-start(%y)
      %nothing = f32[2] add(%a, %b)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 384 * 4096 * 2 + (4 + 8) * 4
    assert out["all-reduce"] == 128 * 4
    assert out["total"] == out["all-gather"] + out["all-reduce"]


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache goes to <checkout>/.jax_cache.  ``config.update``
    is intercepted so that no test turns the cache on."""
    from repro.launch import compile_cache as CC
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    assert CC.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_train_cli_mesh_step_compiles_once(tmp_path):
    """``launch/train.py --mesh host`` on 4 virtual CPU devices (its own
    process: this one sees one device): the step and the data stream
    compile during step 0 and never again.  The mesh step returns params
    and state on the placement they came in on; it once handed them back
    sharded, and step 1 compiled again.  The persistent cache is off, so
    a compile cannot hide as a cache hit."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--mesh", "host", "--workers", "11", "--f", "2",
         "--gar", "multi_bulyan", "--attack", "sign_flip"],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "shape={'data': 2, 'model': 2}" in out.stdout, out.stdout
    assert "[train] compiles after step 0: 0" in out.stdout, out.stdout


def test_train_cli_profiles_the_last_steps(tmp_path):
    """``launch/train.py --profile-dir`` traces the last steps (never step
    0, which compiles) with ``jax.profiler``; the trace holds the host's
    ``batch`` / ``dispatch`` / ``wait`` spans of each traced step."""
    from jax.profiler import ProfileData
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_ENABLE_COMPILATION_CACHE="false")
    prof = tmp_path / "prof"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--workers", "7", "--f", "1", "--profile-dir", str(prof)],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert f"[train] profile: steps 1..2 traced -> {prof}" in out.stdout
    [path] = [os.path.join(d, f) for d, _, fs in os.walk(prof)
              for f in fs if f.endswith(".xplane.pb")]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro:"):
                    seen[e.name] = seen.get(e.name, 0) + 1
    assert seen == {f"repro:{n}": 2
                    for n in ("step", "batch", "dispatch", "wait")}
