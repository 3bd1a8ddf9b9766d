"""The reduction from a recorded trace and the compiled HLO to the
per-layer metrics, on small fixtures, with every expected number worked
out by hand."""
import json
from pathlib import Path

import pytest

import trace_reduce as T

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def small_trace() -> T.Trace:
    """Window [100, 1100) ns; device 0 runs a kernel, an XLA fusion that
    overlaps it, a collective, and one op that starts before the window;
    device 1 runs one op.  Host: two rounds and a dispatch span."""
    e = T.Event
    return T.Trace(
        devices={
            0: [e("fusion.1", 50, 100),            # clipped to [100, 150)
                e("_fused_select.1", 200, 300),    # [200, 500)
                e("fusion.7", 400, 200),           # [400, 600), overlaps
                e("all-gather.2", 800, 100),       # [800, 900)
                e("%_pairwise_stats.3", 950, 100)],  # [950, 1050)
            1: [e("fusion.1", 100, 500)],
        },
        host=[e("bench:window", 100, 1000),
              e("bench:round", 100, 500),
              e("bench:round", 600, 480),
              e("bench:dispatch", 650, 100)],
        window=(100.0, 1100.0))


def test_busy_union_and_idle_share():
    tr = small_trace()
    assert T.busy_intervals(tr, 0) == [(100, 150), (200, 600), (800, 900),
                                       (950, 1050)]
    assert T.busy_ns(tr, 0) == 50 + 400 + 100 + 100
    assert T.idle_share(tr, 0) == pytest.approx(1 - 650 / 1000)
    assert T.idle_share(tr, 1) == pytest.approx(0.5)


def test_idle_gaps_named_by_host_activity():
    tr = small_trace()
    assert T.idle_gaps(tr, 0) == [(150, 200), (600, 800), (900, 950),
                                  (1050, 1100)]
    # longest first; the 600-800 gap's midpoint (700) lies in the
    # dispatch span, the innermost host span there
    assert T.longest_gaps(tr, 0, k=2) == [["dispatch", pytest.approx(2e-7)],
                                          ["round", pytest.approx(5e-8)]]
    assert T.host_activity(tr, 1095) == "none"


def test_kernel_and_xla_attribution_from_hlo():
    hlo = (FIXTURES / "hlo_custom_calls.txt").read_text()
    cc = T.custom_call_bytes(hlo)
    assert cc == {
        "_pairwise_stats.3": 4 * (16 * 16 + 16) + 4 * 16 * 2121728,
        "_fused_select.1": 4 * 159744 + 4 * (16 * 159744 + 2 * 5 * 16),
        "custom-call.9": 2 * 8 * 128 + 2 * 8 * 256 + 4 * 4,
    }
    tr = small_trace()
    nbytes, ns = T.kernel_bytes_and_ns(tr, 0, cc)
    assert ns == 300 + 100
    assert nbytes == cc["_fused_select.1"] + cc["_pairwise_stats.3"]
    kernel = T.kernel_picker(cc)
    xla_ns, count = T.op_ns(tr, 0, lambda n: not kernel(n))
    assert (xla_ns, count) == (50 + 200 + 100, 3)


def test_labels_of_tpu_events():
    assert T.op_label('%fusion.4 = f32[99580800]{0:T(1024)} fusion('
                      'f32[5,51865,384]{2,0,1:T(8,128)} %fusion.21), '
                      'kind=kCustom') == "fusion.4 = f32[99580800] fusion"
    label = T.op_label('%_pairwise_stats.81 = (f32[16,16]{1,0:T(8,128)S(1)}'
                       ', f32[1,16]{1,0:T(1,128)}) custom-call(%pad.10)')
    assert label == "_pairwise_stats.81 = (f32[16,16], f32[1,16]) custom-call"
    assert T.instruction(label) == "_pairwise_stats.81"
    assert T.is_collective("all-reduce.3 = f32[4] all-reduce")
    assert not T.is_collective("fusion.2 = f32[4] fusion")


def test_collectives_and_top_ops():
    tr = small_trace()
    ns, count = T.op_ns(tr, 0, T.is_collective)
    assert (ns, count) == (100, 1)
    assert T.top_ops(tr, 0, k=2) == [
        ["_fused_select.1", pytest.approx(3e-7)],
        ["fusion.7", pytest.approx(2e-7)]]


def test_units_per_window():
    tr = small_trace()
    assert T.spans_in_window(tr, "round") == 2
    assert T.spans_in_window(tr, "step") == 0


def test_recorded_chip_trace():
    """Two rounds of ``whisper-tiny.agg`` recorded on a v5e (PR 12) and
    kept in the reduced form: 41 ``pairwise_stats`` and 35 ``fused_select``
    calls a round (the program's own count of its launches), a chip busy
    through the window, and the six big leaves' XLA apply on top."""
    tr = T.load(str(FIXTURES / "whisper_agg_v5e.json"))
    names = json.loads((FIXTURES / "whisper_agg_v5e.kernels.json")
                       .read_text())["kernel_names"]
    dev = min(tr.devices)
    assert T.spans_in_window(tr, "round") == 2
    ns, count = T.op_ns(tr, dev, T.kernel_picker(names))
    assert count == 2 * (41 + 35)
    assert 0 < ns < 0.01 * tr.window_ns
    assert T.idle_share(tr, dev) < 0.01
    top = T.top_ops(tr, dev, k=1)[0]
    assert top[0] == "fusion.4 = f32[99580800] fusion"   # 5 x 51865 x 384
    assert {g[0] for g in T.longest_gaps(tr, dev)} <= {
        "wait", "dispatch", "round", "none"}


class _Runner:
    unit = "round"

    def flops_per_unit(self):
        return 1e6

    def least_bytes_per_unit(self):
        return 4096.0

    def hlo_text(self):
        return (FIXTURES / "hlo_custom_calls.txt").read_text()


def test_readers_of_an_agg_cell():
    import harness
    cell = harness.Cell.load("whisper-tiny.agg")
    peaks = harness.load_json(harness.HERE / "peaks.json")["devices"][
        "TPU v5 lite"]
    ctx = harness.layer_context(cell, _Runner(), small_trace(), peaks)
    got = harness.per_layer(cell, ctx)
    assert set(got) == {m["name"] for m in cell.per_layer}
    window_s = 1000e-9
    assert got["idle_share.agg"]["value"] == pytest.approx(35.0)
    # busy 650 ns less the kernels' 400 ns, over 2 rounds, in ms
    assert got["xla_ms.agg"]["value"] == pytest.approx(250e-6 / 2)
    assert got["round_hbm_share"]["value"] == pytest.approx(
        100 * 4096 * 2 / window_s / 819e9)
    assert got["mfu.agg"]["value"] == pytest.approx(
        100 * 1e6 * 2 / window_s / 197e12)
    cc = ctx.kernel_bytes
    assert got["pallas_roofline.agg"]["value"] == pytest.approx(
        100 * (cc["_fused_select.1"] + cc["_pairwise_stats.3"]) / 819e9
        / 400e-9)
