"""The main path's Pallas kernels compile for a described (not attached)
TPU v5e chip at real widths.

Interpret mode cannot see what the chip's compiler refuses (unsupported
primitives such as ``sort``, misaligned slices, VMEM overruns), so each
kernel is lowered with ``interpret=False`` against one device of a
described ``v5e:2x2`` topology and compiled.  Nothing runs; these tests
say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D = 1_000_000
#: whisper-tiny's token embedding (vocab 51865 × d_model 384), the widest
#: leaf of the smoke model
WHISPER_EMBED = 51865 * 384


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pairwise_stats_compiles(one_chip):
    x = _spec((15, D), jnp.float32, one_chip)
    text = _compiled_text(lambda a: ops.pairwise_stats(a, interpret=False), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16])
def test_dequant_stats_compiles(one_chip, dtype):
    p = _spec((15, D), dtype, one_chip)
    m = _spec((15,), jnp.float32, one_chip)
    text = _compiled_text(
        lambda a, b: ops.dequant_stats(a, b, interpret=False), p, m)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,f,d,dtype", [
    (11, 2, WHISPER_EMBED, jnp.float32),   # θ = 5, the smoke's widest leaf
    (15, 3, D, jnp.bfloat16),              # θ = 7, odd-θ median
    (12, 2, D, jnp.float32),               # θ = 6, even-θ median
])
def test_fused_select_compiles(one_chip, n, f, d, dtype):
    theta = n - 2 * f - 2
    beta = theta - 2 * f
    x = _spec((n, d), dtype, one_chip)
    w = _spec((theta, n), jnp.float32, one_chip)
    text = _compiled_text(
        lambda a, we, wa: ops.fused_select(a, we, wa, beta, interpret=False),
        x, w, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("theta,beta", [(7, 3), (6, 2)])
def test_coord_select_compiles(one_chip, theta, beta):
    g = _spec((theta, D), jnp.float32, one_chip)
    text = _compiled_text(
        lambda a, b: ops.coord_select(a, b, beta, interpret=False), g, g)
    assert "tpu_custom_call" in text


#: each kernel's entry point at a small width, with the name its
#: ``pallas_call`` carries into the compiled program
_KERNELS = {
    "pairwise_sqdist": lambda x: ops.pairwise_sqdist(x, interpret=False),
    "pairwise_stats": lambda x: ops.pairwise_stats(x, interpret=False),
    "pairwise_stats_rect": lambda x: ops.pairwise_stats_rect(
        x[:4], x, interpret=False),
    "dequant_stats": lambda x: ops.dequant_stats(
        x.astype(jnp.int8), jnp.ones(x.shape[0]), interpret=False),
    "dequant_stats_rect": lambda x: ops.dequant_stats_rect(
        x[:4].astype(jnp.int8), jnp.ones(4), x.astype(jnp.int8),
        jnp.ones(x.shape[0]), interpret=False),
    "fused_select": lambda x: ops.fused_select(
        x, jnp.ones((5, 11)), jnp.ones((5, 11)), 1, interpret=False),
    "coord_select": lambda x: ops.coord_select(x[:5], x[:5], 1,
                                               interpret=False),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_carries_its_name(one_chip, name):
    """The chip's HLO names each kernel's custom call after the kernel
    (``fused_select.3``) and keeps the name in its ``op_name``: that is
    what a device profile and the benchmark's reduction read."""
    import re
    x = _spec((11, 8192), jnp.float32, one_chip)
    calls = [line for line in _compiled_text(_KERNELS[name], x).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT\s+)?%{name}(\.\d+)? = ", line), line
        assert f"/{name}/pallas_call" in line


def test_sharded_stats_compile_to_row_blocks(topo, monkeypatch):
    """The mesh-native stats on a described 2x2 (data, model) mesh: each
    device's rectangular kernel contracts only its row block of the
    worker axis against the gathered stack."""
    import re

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core import api
    # the backend seen here is the CPU; the kernels must compile, not
    # interpret, for the described chip
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    ctx = api.MeshContext.for_mesh(mesh)
    n, d = 11, 4096
    g = _spec((n, d), jnp.float32, NamedSharding(mesh, P()))
    text = _compiled_text(lambda x: api.compute_stats(
        {"w": x}, 2, needs_dists=True, use_pallas=True, mesh_ctx=ctx).dists,
        g)
    # 11 rows pad to 12 over the 2 data shards: a 6-row block per device,
    # 8 after sublane padding, against the 16-row padded gathered stack
    blocks = re.findall(r"operand_layout_constraints=\{f32\[(\d+),(\d+)\]"
                        r"\{1,0\}, f32\[(\d+),\d+\]", text)
    assert blocks and "tpu_custom_call" in text
    assert all((int(a), int(b), int(c)) == (8, d, 16) for a, b, c in blocks)


def test_mesh_step_returns_replicated_params(topo, monkeypatch):
    """The stacked step with ``shard_map_mesh=``, jitted by
    ``jit_train_step`` on a described 2x2 mesh, hands params and state
    back replicated, the placement they came in on, so the next step
    reuses the executable.  Left to the compiler, some came back sharded
    over ``model`` and step 1 compiled again."""
    import functools

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from helpers import reduced_cfg
    from repro import models as MD
    from repro.configs import RobustConfig
    from repro.dist import init_train_state, jit_train_step, make_train_step
    from repro.optim import constant, make_optimizer
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())
    cfg = reduced_cfg("qwen2-1.5b")
    n, per_worker, seq = 11, 2, 16
    opt = make_optimizer("sgd", momentum=0.9)
    params = jax.eval_shape(functools.partial(MD.init_model, cfg=cfg),
                            jax.random.key(0))
    state = jax.eval_shape(lambda p: init_train_state(opt, p), params)
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n, per_worker) + s.shape[1:],
                                       s.dtype),
        MD.make_batch(cfg, "train", n * per_worker, seq, as_spec=True))
    key = jax.eval_shape(lambda: jax.random.key(0))
    step = make_train_step(cfg, RobustConfig(n_workers=n, f=2,
                                             use_pallas=True),
                           opt, constant(0.05), chunk_q=seq,
                           shard_map_mesh=mesh)
    args = jax.tree.map(lambda s: _spec(s.shape, s.dtype, rep),
                        (params, state, batch, key))
    compiled = jit_train_step(step, mesh).lower(*args).compile()
    out = jax.tree.leaves(compiled.output_shardings[:2])
    assert out and all(s.is_equivalent_to(rep, 2) for s in out)
    assert "tpu_custom_call" in compiled.as_text()


def _instructions(text: str, name: str) -> list:
    import re
    return [line for line in text.splitlines()
            if re.match(rf"\s*(ROOT\s+)?%{name}(\.\d+)? = ", line)]


def _sorts(text: str) -> list:
    return [line for line in text.splitlines() if " sort(" in line]


def test_apply_of_a_big_leaf_runs_fused_select(one_chip, monkeypatch):
    """``aggregate_tree`` under ``use_pallas`` on whisper's vocabulary leaf
    (19.9M coordinates): the apply is one ``fused_select`` call and sorts
    nothing; the round's only sort is the plan's over the (n, n) scores."""
    from repro.core import api
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    g = _spec((11, 51865, 384), jnp.float32, one_chip)
    text = _compiled_text(lambda x: api.aggregate_tree(
        {"w": x}, 2, "multi_bulyan", use_pallas=True), g)
    assert len(_instructions(text, "fused_select")) == 1
    sorts = _sorts(text)
    assert not [s for s in sorts if "robust.apply" in s]
    assert all("f32[11,11]" in s and "robust.plan" in s for s in sorts)


def test_sharded_apply_of_a_big_leaf_runs_fused_select(topo, monkeypatch):
    """The mesh-native apply (``_sharded_apply_leaf``) on a described 2x2
    (data, model) mesh, each device's share of the leaf past 1e6
    coordinates: every device runs ``fused_select`` on its (n, d/2)
    block, and nothing under ``robust.apply`` sorts."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core import api
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    ctx = api.MeshContext.for_mesh(mesh)
    n, d = 11, 2 * 1_048_704
    g = _spec((n, d), jnp.float32, NamedSharding(mesh, P()))
    text = _compiled_text(lambda x: api.aggregate_tree(
        {"w": x}, 2, "multi_bulyan", use_pallas=True, mesh_ctx=ctx), g)
    calls = _instructions(text, "fused_select")
    # 11 rows pad to 12 over the 2 data shards; d halves over 'model'
    assert len(calls) == 1 and "f32[12,1048704]" in calls[0]
    assert not [s for s in _sorts(text) if "robust.apply" in s]
