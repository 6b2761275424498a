"""``bench/trace.py`` on a small trace recorded on a TPU v5e
(``record_trace.py``: inside ``bench.window``, four rounds of a 5 ms
``bench.host_wait`` sleep and a ``bench.compute`` matrix product)."""
import pathlib

import pytest

import harness

trace = harness.load_module("trace.py", "test_bench_trace")
DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(DATA / "tiny.xplane.pb"))


def test_window_is_the_window_span(red):
    win = [s for s in red.spans if s[0] == "bench.window"]
    assert len(win) == 1
    assert red.window_s == pytest.approx((win[0][2] - win[0][1]) / 1e9)
    assert 0.02 < red.window_s < 1.0


def test_busy_is_the_union_of_device_ops(red):
    assert 0.0 < red.busy_s < red.window_s
    # four sleeps of 5 ms leave the device idle most of the window
    assert 1.0 - red.busy_s / red.window_s > 0.5
    assert red.op_seconds(lambda op: True) >= red.busy_s * (1 - 1e-9)


def test_idle_gaps_are_labelled_by_host_spans(red):
    longest = [label for _, label in red.gaps[:4]]
    assert longest == ["host_wait"] * 4
    # each sleep is 5 ms; host and device clocks agree to well under 1 ms
    assert all(s >= 0.004 for s, _ in red.gaps[:4])
    assert red.gaps_by_label()[0][0] == "host_wait"


def test_breakdown_lists_at_most_ten_of_each(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
    # the matrix product and the tanh, fused
    assert b["device_ops"][0][0] == "convolution_tanh_fusion kOutput"


def test_parse_op_names_base_and_kind():
    assert trace.parse_op("%fusion.697 = (f32[4]{0}) fusion(f32[4] %a), "
                          "kind=kLoop, calls=%fused_computation.48") == (
        "fusion.697", "fusion", "kLoop")
    assert trace.parse_op("%exsdotp_gemm_pallas.328 = bf16[8,128]{1,0} "
                          "custom-call(u8[8,128] %x)")[1] == \
        "exsdotp_gemm_pallas"
    assert trace.parse_op("%fusion.705.remat = f32[2]{0} fusion()")[1] == \
        "fusion"
    assert trace.parse_op("%while.135 = (s32[]) while(s32[] %t)")[1] == \
        "while"


def test_matmul_instructions_find_dot_fusions():
    hlo = "\n".join([
        "%fused_computation.1 (p0: bf16[8,8], p1: bf16[8,8]) -> f32[8,8] {",
        "  %p0 = bf16[8,8]{1,0} parameter(0)",
        "  %p1 = bf16[8,8]{1,0} parameter(1)",
        "  ROOT %convolution.3 = f32[8,8]{1,0} convolution(%p0, %p1), "
        "dim_labels=bf_io->bf",
        "}",
        "%fused_computation.2 (p0: f32[8]) -> f32[8] {",
        "  %p0 = f32[8]{0} parameter(0)",
        "  ROOT %tanh.1 = f32[8]{0} tanh(%p0)",
        "}",
        "ENTRY %main.9 (a: bf16[8,8], b: bf16[8,8]) -> f32[8,8] {",
        "  %a = bf16[8,8]{1,0} parameter(0)",
        "  %b = bf16[8,8]{1,0} parameter(1)",
        "  %fusion.4 = f32[8,8]{1,0} fusion(%a, %b), kind=kOutput, "
        "calls=%fused_computation.1",
        "  %fusion.5 = f32[8]{0} fusion(%x), kind=kLoop, "
        "calls=%fused_computation.2",
        "  ROOT %dot.6 = f32[8,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}",
        "}",
    ])
    assert trace.matmul_instructions(hlo) == {"convolution.3", "fusion.4",
                                              "dot.6"}


def test_merge_and_clip():
    assert trace._merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    assert trace._clip([(0, 4), (6, 8), (9, 12)], 2, 10) == [
        (2, 4), (6, 8), (9, 10)]
