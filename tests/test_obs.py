"""repro.obs: jit-safe metrics registry, span ring, snapshots (DESIGN.md §14).

The two contracts money rides on:

* **disabled = uninstrumented, bitwise** — ``obs=None`` and
  ``ObsConfig(enabled=False)`` must produce the *identical jaxpr* of the
  step that never heard of observability, and the enabled path must not
  perturb the training computation (params bitwise equal);
* **the registry is exact** — histogram counts match numpy's
  ``searchsorted`` semantics under ``lax.scan``, the ring drains in seq
  order across wraparound, and the whole ``mstate`` survives a
  checkpoint round-trip.

The golden-summary regression pins the ``sim.campaign.v1`` digest
byte-for-byte across the telemetry→obs accumulator port.
"""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs as OBS
from repro.checkpoint import restore, save
from repro.configs.base import ArchConfig, RobustConfig
from repro.data import lm_batches
from repro.dist import init_train_state, make_train_step, split_workers
from repro import models as MD
from repro.optim import constant, sgd

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "fixtures_obs", "golden_summary.json")

KEY = jax.random.key(0)
ARCH = ArchConfig(name="obs-tiny", family="dense", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64)
N, F = 7, 1


def _setup(**rkw):
    rcfg = RobustConfig(n_workers=N, f=F, gar="multi_bulyan", **rkw)
    params = MD.init_model(KEY, ARCH)
    opt = sgd(momentum=0.9)
    state = init_train_state(opt, params, n_workers=N)
    batch = split_workers(next(lm_batches(ARCH.vocab_size, N * 2, 16,
                                          seed=3)), N)
    return rcfg, params, opt, state, batch


def _step(rcfg, opt, **kw):
    return make_train_step(ARCH, rcfg, opt, constant(0.05), chunk_q=16,
                           **kw)


# ------------------------------------------------------- disabled = noop
def test_disabled_obs_is_bitwise_noop():
    rcfg, params, opt, state, batch = _setup()
    base = _step(rcfg, opt)
    off = _step(rcfg, opt, obs=OBS.ObsConfig(enabled=False))
    j0 = str(jax.make_jaxpr(base)(params, state, batch, KEY))
    j1 = str(jax.make_jaxpr(off)(params, state, batch, KEY))
    assert j0 == j1, "ObsConfig(enabled=False) changed the step jaxpr"


def test_disabled_obs_state_has_zero_leaves():
    assert OBS.init_train_obs(None, N) is None
    assert OBS.init_train_obs(OBS.ObsConfig(enabled=False), N) is None
    assert jax.tree.leaves(OBS.init_train_obs(
        OBS.ObsConfig(enabled=False), N)) == []


def test_enabled_obs_does_not_perturb_training():
    rcfg, params, opt, state, batch = _setup()
    base = jax.jit(_step(rcfg, opt))
    on = jax.jit(_step(rcfg, opt, obs=OBS.ObsConfig(enabled=True)))
    p0, s0, p1, s1 = params, state, params, state
    for i in range(2):
        k = jax.random.fold_in(KEY, i)
        p0, s0, m0 = base(p0, s0, batch, k)
        p1, s1, m1 = on(p1, s1, batch, k)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(s1.mstate["m"].counters["rounds"]) == 2.0
    assert s0.mstate is None


def test_enabled_step_records_spans_in_pipeline_order():
    rcfg, params, opt, state, batch = _setup()
    on = jax.jit(_step(rcfg, opt, obs=OBS.ObsConfig(enabled=True)))
    p, s = params, state
    for i in range(2):
        p, s, _ = on(p, s, batch, jax.random.fold_in(KEY, i))
    recs = OBS.drain(s.mstate["t"])
    assert [(r["round"], r["phase"]) for r in recs] == [
        (0, "stats"), (0, "plan"), (0, "apply"),
        (1, "stats"), (1, "plan"), (1, "apply")]


# ------------------------------------------------------------- registry
def test_histogram_exact_vs_numpy_under_scan():
    edges = (0.5, 1.5, 2.5, 4.0)
    spec = OBS.MetricsSpec(counters=("n",), hists=(("v", edges),))
    rng = np.random.default_rng(7)
    vals = rng.uniform(-1.0, 6.0, size=64).astype(np.float32)

    def body(m, v):
        m = OBS.inc(m, "n")
        return OBS.observe(m, "v", v), ()

    m, _ = jax.lax.scan(body, OBS.init_metrics(spec), jnp.asarray(vals))
    want = np.bincount(
        np.searchsorted(np.asarray(edges), vals, side="right"),
        minlength=len(edges) + 1)
    np.testing.assert_array_equal(np.asarray(m.hists["v"]), want)
    assert float(m.counters["n"]) == len(vals)


def test_vector_observe_counts_every_element():
    spec = OBS.MetricsSpec(hists=(("age", (0.5, 1.5)),))
    m = OBS.observe(OBS.init_metrics(spec), "age",
                    jnp.asarray([0.0, 1.0, 1.0, 2.0]))
    np.testing.assert_array_equal(np.asarray(m.hists["age"]), [1, 2, 1])


def test_unknown_names_are_noops_and_none_passes_through():
    spec = OBS.MetricsSpec(counters=("a",))
    m = OBS.init_metrics(spec)
    assert OBS.inc(m, "nope") is m
    assert OBS.observe(m, "nope", 1.0) is m
    assert OBS.inc(None, "a") is None
    assert OBS.record(None, OBS.PH_STATS, 0) is None


def test_ring_wraparound_drains_in_seq_order():
    t = OBS.init_trace(4)
    for i in range(11):
        t = OBS.record(t, i % len(OBS.PHASES), i, payload=float(i))
    recs = OBS.drain(t)
    assert [r["seq"] for r in recs] == [7, 8, 9, 10]
    assert [r["payload"] for r in recs] == [7.0, 8.0, 9.0, 10.0]
    assert int(t.head) == 11


def test_mstate_checkpoint_round_trip(tmp_path):
    ms = OBS.init_train_obs(OBS.ObsConfig(enabled=True), N, telemetry=True)
    ms = {"m": OBS.observe(OBS.inc(ms["m"], "rounds", 3.0),
                           "agg_grad_norm", 2.5),
          "t": OBS.record(ms["t"], OBS.PH_PLAN, 1, 0.25)}
    save(str(tmp_path), 0, {"mstate": ms})
    like = {"mstate": OBS.init_train_obs(OBS.ObsConfig(enabled=True), N,
                                         telemetry=True)}
    back = restore(str(tmp_path), 0, like)["mstate"]
    assert float(back["m"].counters["rounds"]) == 3.0
    np.testing.assert_array_equal(np.asarray(back["m"].hists["agg_grad_norm"]),
                                  np.asarray(ms["m"].hists["agg_grad_norm"]))
    assert OBS.drain(back["t"]) == OBS.drain(ms["t"])


def test_spec_rejects_duplicates_and_bad_edges():
    with pytest.raises(ValueError, match="duplicate"):
        OBS.MetricsSpec(counters=("a", "a"))
    with pytest.raises(ValueError, match="sorted"):
        OBS.MetricsSpec(hists=(("h", (2.0, 1.0)),))
    with pytest.raises(ValueError, match="ring capacity"):
        OBS.ObsConfig(enabled=True, ring=0)


# ------------------------------------------------------------- snapshot
def test_snapshot_validates_and_catches_corruption():
    ms = OBS.init_train_obs(OBS.ObsConfig(enabled=True), N)
    snap = OBS.snapshot(metrics=ms["m"], trace_records=OBS.drain(ms["t"]))
    assert OBS.validate_snapshot(snap) == []
    bad = json.loads(json.dumps(snap))
    bad["metrics"]["hists"]["agg_grad_norm"]["counts"] = [0]
    bad["schema"] = "obs.v0"
    problems = OBS.validate_snapshot(bad)
    assert any("schema" in p for p in problems)
    assert any("edges+1" in p for p in problems)


# ------------------------------------------------- golden campaign summary
def test_campaign_summary_golden():
    """The telemetry→obs port must not move a single byte of the
    ``sim.campaign.v1`` summary (the digest now lives in
    ``obs.export.phase_summary``; ``telemetry.summarize`` delegates).
    The golden holds the random streams of ``jax_threefry_partitionable``
    on, JAX's default since 0.5: with it off, the summary is the one the
    file held under older JAX, to float digits."""
    from repro.sim.engine import run_campaign
    from repro.sim.scenario import AttackPhase, AttackSchedule, Scenario
    sc = Scenario(name="obs-golden", arch=ARCH, n_workers=N, f=F,
                  seed=0, per_worker_batch=2, seq=16, lr=0.05,
                  schedule=AttackSchedule(phases=(
                      AttackPhase(attack="none", steps=2),
                      AttackPhase(attack="sign_flip", steps=2))))
    got = json.dumps(run_campaign(sc).summary, sort_keys=True)
    with open(GOLDEN) as fh:
        assert got == fh.read().strip()


def test_kernel_profiler_records_fused_select_for_every_apply_leaf():
    """Every apply leaf under ``use_pallas`` is a ``fused_select`` launch
    in the profile, whatever its size, and no leaf goes to XLA."""
    from repro.core import api
    G = jax.random.normal(KEY, (11, 500))
    tree = {"small": G[:, :200], "big": G[:, 200:]}
    with OBS.KernelProfiler() as prof:
        api.aggregate_tree(tree, 2, "multi_bulyan", use_pallas=True)
    got = sorted((r.kernel, r.d) for r in prof.records)
    assert got == [("fused_select", 200), ("fused_select", 300),
                   ("pairwise_stats", 200), ("pairwise_stats", 300)]
    assert not any(r.kernel.startswith("xla:") for r in prof.records)


# --------------------------------------------------------- host spans, export
def test_export_puts_ring_records_at_their_step_end(tmp_path):
    """Ring records carry no time: the export makes them instants at the
    end of their round's host ``step`` span, and leaves out (and counts)
    records of rounds with no span."""
    from repro.launch.obs_report import _trace_problems
    spans = [{"name": "step", "ts_us": 10.0, "dur_us": 5.0,
              "args": {"round": 0}},
             {"name": "dispatch", "ts_us": 11.0, "dur_us": 1.0, "args": {}},
             {"name": "step", "ts_us": 20.0, "dur_us": 4.0,
              "args": {"round": 1}}]
    recs = [{"seq": s, "round": r, "phase": p, "payload": 0.5}
            for s, (r, p) in enumerate([(0, "stats"), (0, "apply"),
                                        (1, "plan"), (2, "stats")])]
    path = str(tmp_path / "t.json")
    n = OBS.export_chrome_trace(path, device_records=recs, host_spans=spans)
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    assert n == len(events)
    ring = [e for e in events if e["pid"] == 1 and e["ph"] != "M"]
    assert [(e["name"], e["ph"], e["ts"]) for e in ring] == [
        ("stats", "i", 15.0), ("apply", "i", 15.0), ("plan", "i", 24.0)]
    assert all("dur" not in e for e in ring)
    assert doc["otherData"]["unanchored_records"] == 1
    host = [e for e in events if e["pid"] == 0 and e["ph"] == "X"]
    assert [(e["name"], e["ts"], e["dur"]) for e in host] == [
        ("step", 10.0, 5.0), ("dispatch", 11.0, 1.0), ("step", 20.0, 4.0)]
    assert _trace_problems(events) == []
    # an invented device duration is what --validate refuses
    bad = dict(ring[0], ph="X", dur=2.5)
    assert _trace_problems(events + [bad])


def test_span_tracer_writes_into_the_profiler_trace(tmp_path):
    """Host spans land in a ``jax.profiler`` trace as ``repro:<name>``, on
    the profiler's clock, a step span as the step it is."""
    from jax.profiler import ProfileData
    tracer = OBS.SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(2):
            with tracer.span("step", round=i):
                with tracer.span("dispatch"):
                    x = jnp.ones(8) * i
                with tracer.span("wait"):
                    jax.block_until_ready(x)
    finally:
        jax.profiler.stop_trace()
    [path] = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
              for f in fs if f.endswith(".xplane.pb")]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro:"):
                    seen[e.name] = seen.get(e.name, 0) + 1
    assert seen == {"repro:step": 2, "repro:dispatch": 2, "repro:wait": 2}
    assert [s["name"] for s in tracer.spans] == [
        "dispatch", "wait", "step", "dispatch", "wait", "step"]
