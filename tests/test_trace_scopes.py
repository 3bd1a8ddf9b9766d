"""The robust step's phases are named in the compiled program.

``repro.obs.scope`` puts ``robust.<phase>`` into the ``op_name`` metadata
of every instruction XLA emits for a phase, which is what a device
profile shows of it.  These tests compile small steps on the CPU and read
the optimized HLO: every phase is there, the workers' backward pass is
told apart from their forward pass, no phase is nested in itself, and
every matrix product and sort of the step belongs to some phase.
"""
from __future__ import annotations

import re

import jax
import pytest

from repro import obs as OBS
from repro.configs.base import ArchConfig, RobustConfig
from repro.core import api
from repro.data import lm_batches
from repro.dist import init_train_state, make_train_step, split_workers
from repro.dist.streaming import make_streaming_train_step
from repro import models as MD
from repro.optim import constant, sgd

KEY = jax.random.key(0)
ARCH = ArchConfig(name="scope-tiny", family="dense", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64)
N, F = 7, 1
ALL = set(OBS.SCOPES)
AGGREGATION = {"stats", "plan", "apply"}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(re.escape(OBS.SCOPE_PREFIX) + r"(\w+)")


def instructions(hlo: str):
    """(name, opcode, op_name) of every instruction of the HLO text,
    fusion bodies included."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            on = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), on.group(1) if on else ""))
    return out


def scopes_of(op_name: str):
    return _SCOPE.findall(op_name)


def _train_args():
    params = MD.init_model(KEY, ARCH)
    opt = sgd(momentum=0.9)
    state = init_train_state(opt, params, n_workers=N)
    batch = split_workers(next(lm_batches(ARCH.vocab_size, N * 2, 16,
                                          seed=3)), N)
    return opt, (params, state, batch, KEY)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def stacked_hlo(**kw) -> str:
    opt, args = _train_args()
    rcfg = RobustConfig(n_workers=N, f=F, gar="multi_bulyan")
    step = make_train_step(ARCH, rcfg, opt, constant(0.05), chunk_q=16,
                           attack="sign_flip", **kw)
    return _compile(step, *args)


def streaming_hlo(scope: str) -> str:
    opt, args = _train_args()
    rcfg = RobustConfig(n_workers=N, f=F, gar="multi_bulyan")
    step = make_streaming_train_step(ARCH, rcfg, opt, constant(0.05),
                                     scope=scope, chunk_q=16,
                                     attack="sign_flip")
    return _compile(step, *args)


def aggregate_hlo() -> str:
    stack = {"w": jax.ShapeDtypeStruct((N, 64, 48), jax.numpy.float32),
             "b": jax.ShapeDtypeStruct((N, 48), jax.numpy.float32)}
    return _compile(lambda g: api.aggregate_tree(g, F), stack)


PROGRAMS = {
    "stacked": (stacked_hlo, ALL),
    "stacked_codec": (lambda: stacked_hlo(codec="qsgd:bits=8"), ALL),
    "streaming_global": (lambda: streaming_hlo("global"), ALL),
    "streaming_block": (lambda: streaming_hlo("block"), ALL),
    "aggregate_tree": (aggregate_hlo, AGGREGATION),
}


@pytest.fixture(scope="module")
def program():
    """Each program's instructions, compiled once for the module."""
    cache = {}

    def get(name: str):
        if name not in cache:
            cache[name] = instructions(PROGRAMS[name][0]())
        return cache[name]
    return get


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_program_carries_exactly_its_phases(program, name):
    found = {s for _, _, on in program(name) for s in scopes_of(on)}
    assert found == PROGRAMS[name][1]


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_every_product_and_sort_belongs_to_a_phase(program, name):
    loose = [(n, on) for n, opc, on in program(name)
             if opc in ("dot", "sort") and not scopes_of(on)]
    assert not loose, loose[:5]
    assert any(opc == "dot" for _, opc, _ in program(name))


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_no_phase_is_nested_in_itself(program, name):
    for n, _, on in program(name):
        found = scopes_of(on)
        assert len(found) == len(set(found)), (n, on)


@pytest.mark.parametrize("name", ["stacked", "streaming_global"])
def test_workers_backward_is_told_from_forward(program, name):
    ops = program(name)
    work = [on for _, _, on in ops if scopes_of(on) == ["workers"]]
    back = [on for on in work if "transpose(jvp(" in on]
    fwd = [on for on in work if "jvp(" in on and "transpose(" not in on]
    assert back and fwd
    # the backward pass is nowhere else
    assert all(scopes_of(on) == ["workers"] for _, _, on in ops
               if "transpose(jvp(" in on)


def test_aggregation_kernels_are_named_phases():
    """The Pallas path (interpret mode on the CPU) keeps the kernel's name
    under its phase: stats kernels under ``robust.stats``, the fused
    select under ``robust.apply``."""
    stack = {"w": jax.ShapeDtypeStruct((N, 4096), jax.numpy.float32)}
    ops = instructions(_compile(
        lambda g: api.aggregate_tree(g, F, use_pallas=True,
                                     fused="force"), stack))
    names = {on for _, _, on in ops}
    assert any(scopes_of(on) == ["stats"] and "/pairwise_stats/" in on
               for on in names)
    assert any(scopes_of(on) == ["apply"] and "/fused_select/" in on
               for on in names)


def test_unknown_scope_is_refused():
    with pytest.raises(ValueError, match="unknown scope"):
        OBS.scope("forward")
