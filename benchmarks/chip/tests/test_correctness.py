"""The check that decides ``correct`` fails where it should, at the
rehearsal sizes on the CPU: the control (the reference one precision
below the configuration's, in the program's place) and each fault of the
cell's kind (the timed path broken underneath) come out not correct
under the cell's own limits, while the sound program passes a number
that the fault fails.  The chip readings that set the limits are in
PERF.md; this keeps the comparison from going blind."""
import time

import pytest

import harness

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]]


def kind_of(name):
    return harness.Cell.load(name, rehearse=True).kind


def run(name, variant):
    return harness.run(name, seed=2 ** 33 + 5, seconds=0.5, trace=False,
                       rehearse=True, variant=variant,
                       t0=time.perf_counter(), log=lambda *a: None)


def failing(result):
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS for fault in kind_of(name).FAULTS])
def test_fault_is_not_correct(name, fault):
    sound, broken = run(name, "program"), run(name, fault)
    assert not broken["correct"], broken["checks"]
    assert failing(broken) - failing(sound), (sound["checks"],
                                              broken["checks"])


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = harness.Cell.load(name, rehearse=True)
    jax = harness.setup_jax(cache=False)
    runner = cell.kind.Runner(cell, jax, jax.devices()[:1],
                              log=lambda *a: None)
    runner.build()
    runner.prepare(7)
    runner.finish()
    runner.check(7)
    control = runner.control()
    assert any(not v <= cell.limits[k] for k, v in control.items()), control
