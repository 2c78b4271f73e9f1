"""The trace reduction of bench/trace_reduce.py on hand-built events."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import trace_reduce as tr  # noqa: E402

E = tr.Event


def test_union_merges_overlapping_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [(0, 4), (5, 7), (10, 11)]


def test_gaps_are_the_uncovered_parts_of_the_window():
    busy = [(0, 4), (5, 7), (10, 11)]
    assert tr.gaps(busy, 2, 12) == [(4, 5), (7, 10), (11, 12)]
    assert tr.gaps([], 0, 3) == [(0, 3)]
    assert tr.gaps([(0, 10)], 2, 8) == []


def test_reduce_events_busy_union_kernel_sums_and_gap_names():
    # two overlapping ops count once towards busy; op time sums each event
    ops = [E("panther_opa_fused.1", 100, 300), E("fusion.2", 200, 200),
           E("panther_opa_fused.1", 600, 100), E("convolution.3", 900, 200)]
    host = [E("bench.window", 0, 1000), E("bench.dispatch", 0, 90),
            E("bench.batch", 450, 150), E("bench.wait", 700, 200)]
    s = tr.reduce_events([ops], host, (0, 1000))
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [100,400] + [600,700] + [900,1000] (clipped at the window's end)
    assert s.busy_s == pytest.approx(500e-9)
    assert s.op_s["panther_opa_fused.1"] == pytest.approx(400e-9)
    assert s.op_calls["panther_opa_fused.1"] == 2
    assert s.op_s["convolution.3"] == pytest.approx(100e-9)
    assert [n for n, _ in s.idle_gaps] == ["bench.batch", "bench.wait", "bench.dispatch"]
    assert [round(sec * 1e9) for _, sec in s.idle_gaps] == [200, 200, 100]
    assert s.top_ops(1) == [["panther_opa_fused.1", pytest.approx(400e-9)]]


def test_reduce_events_averages_busy_over_devices():
    a = [E("x", 0, 100)]
    b = [E("x", 0, 50)]
    s = tr.reduce_events([a, b], [], (0, 100))
    assert s.busy_s == pytest.approx(75e-9)
    assert s.idle_gaps == [("other", pytest.approx(50e-9))]


HLO = (
    '  %panther_opa_fused.7 = s8[8,256,512]{2,1,0:T(8,128)(4,1)} custom-call(%a, %b, %c, %d, %e), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[1,2]{1,0}, '
    'f32[512,256]{1,0}, f32[512,512]{1,0}, s8[8,256,512]{2,1,0}, s32[1,2]{1,0}}, '
    'output_to_operand_aliasing={{}: (3, {})}, metadata={op_name="x"}\n'
    '  ROOT %fusion.3 = f32[4]{0} fusion(%q), kind=kLoop\n'
)


def test_kernel_calls_reads_operand_shapes_of_custom_calls():
    calls = tr.kernel_calls(HLO)
    assert list(calls) == ["panther_opa_fused.7"]
    c = calls["panther_opa_fused.7"]
    assert c["operands"] == [("f32", (1, 2)), ("f32", (512, 256)), ("f32", (512, 512)),
                             ("s8", (8, 256, 512)), ("s32", (1, 2))]
    assert c["result"] == [("s8", (8, 256, 512))]
    assert tr.family("panther_opa_fused.7") == "panther_opa_fused"


def test_roofline_share_is_least_time_over_device_time():
    calls = tr.kernel_calls(HLO)
    s = tr.Summary(window_s=1.0, busy_s=1.0, op_s={"panther_opa_fused.7": 2e-6, "fusion.3": 1.0},
                   op_calls={"panther_opa_fused.7": 2, "fusion.3": 1}, idle_gaps=[])
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # each call: 1e3 FLOPs -> 1e-9 s; 500 bytes -> 5e-7 s: memory bound
    share = tr.roofline_share(s, calls, lambda f: f == "panther_opa_fused",
                              lambda call: (1e3, 500, "bf16_flops_per_s"), peaks)
    assert share == pytest.approx(100.0 * 2 * 5e-7 / 2e-6)
    assert tr.roofline_share(s, calls, lambda f: f == "panther_crs", None, peaks) is None


def test_trace_recorded_on_the_chip():
    """A 2.3 s traced window of phi4-train on one TPU v5 lite (14 steps):
    device plane ``/device:TPU:0``, line ``XLA Ops`` named by HLO text."""
    path = pathlib.Path(__file__).parent / "data" / "phi4-train.xplane.pb"
    s = tr.load_profile(str(path))
    assert 2.3 < s.window_s < 2.4
    assert 0.99 < s.busy_s / s.window_s <= 1.0
    families = {tr.family(n) for n in s.op_s}
    assert {"panther_opa_fused", "panther_opa_deposit"} <= families
    assert not any(f.startswith("panther_mvm") for f in families)
    assert not any(n.startswith(("while", "call")) for n in s.op_s)
    # one deposit of the embedding a step; each layer leaf's fused OPA runs
    # once a layer, in the scan over both layers
    assert s.op_calls["panther_opa_deposit.1"] == 14
    assert {n: c for n, c in s.op_calls.items() if n.startswith("panther_opa_fused")} == {
        f"panther_opa_fused.{i}": 28 for i in range(25, 30)}
    assert s.top_ops(1)[0][0] == "panther_opa_deposit.1"
    assert {name for name, _ in s.idle_gaps} <= set(tr.HOST_SPANS) | {"other"}
