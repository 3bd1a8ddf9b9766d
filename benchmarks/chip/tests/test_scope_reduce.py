"""The reduction of device time to the program's phases
(``scope_reduce``), on a hand-made trace with every number worked out,
and on a trace recorded on a v5e with the device's own record of which
program ran when (``record_scope_fixture.py``)."""
import json
from pathlib import Path

import pytest

import scope_reduce as S
import trace_reduce as T

FIXTURES = Path(__file__).resolve().parent / "fixtures"
STEP = {"while.1 = f32[4] while": "jit(step)/robust.workers/while",
        "fusion.2 = f32[4] fusion": "jit(step)/robust.workers/mul",
        "fusion.3 = f32[8] fusion": "jit(step)/robust.apply/take",
        "copy.4 = f32[8] copy": "jit(step)/copy"}
BATCH = {"fusion.2 = f32[4] fusion": "jit(batch)/add",
         "fusion.3 = f32[2] fusion": "jit(batch)/iota"}


def small_trace() -> T.Trace:
    """Window [0, 100): the step's loop [10, 60) around two body ops, an
    apply fusion, an unscoped copy; then the batch program, whose first
    op has a label the step holds too, and whose second shares only its
    instruction name with a step op."""
    e = T.Event
    return T.Trace(
        devices={0: [e("while.1 = f32[4] while", 10, 50),
                     e("fusion.2 = f32[4] fusion", 12, 8),    # in the loop
                     e("fusion.3 = f32[8] fusion", 30, 20),   # in the loop
                     e("fusion.3 = f32[8] fusion", 65, 10),
                     e("copy.4 = f32[8] copy", 75, 3),
                     e("fusion.2 = f32[4] fusion", 88, 4),    # batch's
                     e("fusion.3 = f32[2] fusion", 92, 5),    # batch's
                     e("fusion.7 = f32[1] fusion", 98, 4)]},  # no program
        host=[e("bench:window", 0, 100)], window=(0.0, 100.0))


def test_loop_counts_only_where_no_body_op_runs():
    tr = small_trace()
    own = S.self_ns(tr.devices[0], *tr.window)
    assert own == [50 - 8 - 20, 8, 20, 10, 3, 4, 5, 2]
    assert sum(own) == T.busy_ns(tr, 0)


def test_colliding_labels_go_to_the_program_running_around_them():
    tr = small_trace()
    assert S.event_programs(tr.devices[0], [STEP, BATCH]) == \
        [0, 0, 0, 0, 0, 1, 1, None]


def test_parts_sum_to_busy_time():
    tr = small_trace()
    parts = S.attribute(tr, 0, [STEP, BATCH])
    assert S.phase_ns(parts) == {"workers": 22 + 8, "apply": 20 + 10,
                                 S.UNSCOPED: 3, S.OTHER: 4 + 5 + 2}
    assert sum(S.phase_ns(parts).values()) == T.busy_ns(tr, 0)
    [[label, op_name, seconds]] = S.unscoped_ops(parts)
    assert (label, op_name) == ("copy.4 = f32[8] copy", "jit(step)/copy")
    assert seconds == pytest.approx(3e-9)


def test_a_program_without_scopes_is_other():
    tr = small_trace()
    unscoped_step = {k: "jit(step)/op" for k in STEP}
    parts = S.attribute(tr, 0, [unscoped_step, BATCH])
    assert set(S.phase_ns(parts)) == {S.OTHER}


def test_phase_is_the_innermost_scope():
    assert S.phase("jit(f)/robust.workers/vmap(transpose(jvp()))/dot") \
        == "workers"
    assert S.phase("jit(f)/robust.stats/jit(_pairwise_stats)/"
                   "pairwise_stats/pallas_call") == "stats"
    assert S.phase("jit(f)/robust.apply/x/robust.plan/y") == "plan"
    assert S.phase("jit(f)/transpose(jvp())/dot") is None


def test_hlo_labels_match_event_labels():
    """An instruction of the compiled text gets the label a device event
    of it gets (``trace_reduce.op_label``), with its ``op_name``."""
    ops = S.hlo_op_names((FIXTURES / "hlo_custom_calls.txt").read_text())
    assert ops["_pairwise_stats.3 = (f32[16,16], f32[1,16]) custom-call"] \
        == "jit(<lambda>)/jit(_pairwise_stats)/pallas_call"
    assert ops["custom-call.9 = bf16[8,128] custom-call"] == ""
    assert T.op_label("%fusion.7 = f32[5,147456]{1,0} fusion(f32[5,11]{1,0} "
                      "%p), kind=kOutput") in ops


def test_kernels_by_name_adds_up_to_every_call():
    e = T.Event
    tr = T.Trace({0: [e("fused_select.1", 10, 5), e("fused_select.2", 20, 5),
                      e("pairwise_stats.1", 30, 2)]},
                 [e("bench:window", 0, 100)], (0.0, 100.0))
    cc = {"fused_select.1": 100.0, "fused_select.2": 50.0,
          "pairwise_stats.1": 8.0}
    assert S.kernels_by_name(tr, 0, cc) == {
        "fused_select": [2, 150.0, 10.0], "pairwise_stats": [1, 8.0, 2.0]}


# ------------------------------------------------------ the recorded trace
@pytest.fixture(scope="module")
def recorded():
    path = FIXTURES / "scopes_v5e.json"
    if not path.exists():
        pytest.skip("no recorded fixture")
    with open(path) as fh:
        d = json.load(fh)
    return (T.Trace.from_json(d["trace"]), d["modules"],
            [(name, ops) for name, ops in d["programs"]])


def _truth(trace, modules):
    """The module the device's own record has running at each event."""
    out = []
    for ev in trace.devices[0]:
        run = [m for m in modules if m[1] <= ev.start_ns < m[1] + m[2]]
        out.append(run[0][0] if run else None)
    return out


def test_recorded_parts_sum_to_busy_time(recorded):
    trace, _, programs = recorded
    parts = S.phase_ns(S.attribute(trace, 0, [p for _, p in programs]))
    assert sum(parts.values()) == pytest.approx(T.busy_ns(trace, 0),
                                                rel=1e-12)
    assert {"workers", "attack", "stats", "plan", "apply", "update"} \
        <= set(parts)


def _part(programs, j, label):
    if j is None or not S.is_scoped(programs[j][1]):
        return S.OTHER
    return S.phase(programs[j][1][label]) or S.UNSCOPED


def test_recorded_events_land_in_the_part_of_the_program_that_ran_them(
        recorded):
    """Without the device's record of programs, every event still gets
    the part (phase, unscoped, other programs) of the program that ran
    it."""
    trace, modules, programs = recorded
    got = S.event_programs(trace.devices[0], [p for _, p in programs])
    lo, hi = trace.window
    wrong = []
    for ev, j, t in zip(trace.devices[0], got, _truth(trace, modules)):
        if t is None or not lo <= ev.start_ns < hi:
            continue
        held = [k for k, (name, ops) in enumerate(programs)
                if name == t and ev.name in ops]
        want = _part(programs, held[0] if held else None, ev.name)
        if _part(programs, j, ev.name) != want:
            wrong.append((ev.name, want))
    assert not wrong, wrong[:5]


def test_recorded_name_collisions_resolve(recorded):
    """Instruction names the step shares with the batch program: each
    event of the batch program lands in other programs."""
    trace, modules, programs = recorded
    named = {name: {T.instruction(label) for label in ops}
             for name, ops in programs}
    step = next(n for n, ops in programs if S.is_scoped(ops))
    truth = _truth(trace, modules)
    parts = S.attribute(trace, 0, [p for _, p in programs])
    colliding = [ev for ev, t in zip(trace.devices[0], truth)
                 if t is not None and t != step
                 and T.instruction(ev.name) in named[step]]
    assert colliding
    by_label = {}
    for label, _, part, _ in parts:
        by_label.setdefault(label, set()).add(part)
    for ev in colliding:
        if ev.name not in dict(programs)[step]:
            assert by_label.get(ev.name, {S.OTHER}) == {S.OTHER}


def test_readers_on_the_recorded_step(recorded, monkeypatch):
    """The phase and kernel readers on the recorded steps, with the
    fixture's programs standing for the ones loaded in the process."""
    import harness
    from metrics import _scopes
    trace, _, programs = recorded
    with open(FIXTURES / "scopes_v5e.json") as fh:
        kernel_bytes = json.load(fh)["kernel_bytes"]
    monkeypatch.setattr(S, "live_programs", lambda jax: programs)
    peaks = harness.load_json(harness.HERE / "peaks.json")["devices"][
        "TPU v5 lite"]
    units = T.spans_in_window(trace, "step")
    ctx = harness.LayerContext(trace, 0, units, trace.window_ns * 1e-9,
                               peaks, 1, None, None, kernel_bytes)
    read = {name: harness.load_module(
        harness.HERE / "metrics" / f"{name}.py").read(ctx)
        for name in ("workers_ms.train", "aggregate_ms.train",
                     "stats_ms.agg", "apply_ms.agg", "stats_roofline.agg",
                     "select_roofline.agg")}
    per = _scopes.phases_ms(ctx)
    busy = T.busy_ns(trace, 0) * 1e-6 / units
    assert sum(per.values()) == pytest.approx(busy, rel=1e-9)
    assert read["workers_ms.train"] == per["workers"] > 0
    assert read["aggregate_ms.train"] == pytest.approx(
        per["stats"] + per["plan"] + per["apply"])
    assert read["stats_ms.agg"] == per["stats"]
    assert read["apply_ms.agg"] == per["apply"]
    # every Pallas call of the step is one of the two named kernels, so
    # their bytes and times add up to pallas_roofline's
    named = {S.kernel_name(k) for k in kernel_bytes}
    assert named == {"pairwise_stats", "fused_select"}
    nb, ns = T.kernel_bytes_and_ns(trace, 0, kernel_bytes)
    parts = [T.kernel_bytes_and_ns(trace, 0, {
        k: v for k, v in kernel_bytes.items() if S.kernel_name(k) == name})
        for name in named]
    assert sum(p[0] for p in parts) == pytest.approx(nb)
    assert sum(p[1] for p in parts) == pytest.approx(ns)
    for name in ("stats_roofline.agg", "select_roofline.agg"):
        assert 0 < read[name] <= 100
