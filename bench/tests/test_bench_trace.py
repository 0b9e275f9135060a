"""The reduction from a profiler trace to device busy time, per-operation
time and idle gaps attributed to the benchmark's host spans."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce  # noqa: E402


def test_busy_idle_and_gaps_on_known_intervals(monkeypatch):
    # window 0..100 ns; ops overlap (10..30, 20..40) and 60..70; the host
    # was inside bench.step over 0..50 and bench.drain over 50..100; a gap
    # goes to the span open at its middle
    fake = {"device": {"/device:TPU:0": [("fused", 10, 30), ("copy", 20, 40),
                                         ("fused", 60, 70), ("late", 95,
                                                             120)]},
            "host": [("bench.window", 0, 100), ("bench.step", 0, 50),
                     ("bench.drain", 50, 100)]}
    monkeypatch.setattr(trace_reduce, "load", lambda _p: fake)
    r = trace_reduce.reduce("x")
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((30 + 10 + 5) * 1e-9)
    assert r["ops"]["fused"] == pytest.approx(30e-9)
    assert r["ops"]["late"] == pytest.approx(5e-9)    # cut at the window
    assert r["gaps"]["bench.step"] == pytest.approx(10e-9)      # 0..10
    assert r["gaps"]["bench.drain"] == pytest.approx(45e-9)     # 40..60,
    assert sum(r["gaps"].values()) == pytest.approx(55e-9)      # 70..95
    assert trace_reduce.top(r["ops"], 1) == [["fused", r["ops"]["fused"]]]



HLO = """\
%fused_computation.7 (param_0: f32[4,8], param_1: s32[]) -> f32[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  %closed_call.13 = f32[8]{0} custom-call(%param_0), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/vmap(jit(fused_step_pallas))/pallas_call"}
  ROOT %dus = f32[4,8]{1,0} dynamic-update-slice(%param_0, %closed_call.13)
}

%body (p: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %closed_call.14 = f32[4,8]{1,0} fusion(%gte.1, %gte.0), kind=kCustom, calls=%fused_computation.7, metadata={op_name="jit(f)/while/body"}
  %other.2 = f32[8]{0} custom-call(%gte.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(sat_aggregate)/pallas_call"}
}

ENTRY %main (a: f32[4,8]) -> f32[4,8] {
  %while.20 = (s32[], f32[4,8]) while(%t), condition=%cond, body=%body
  ROOT %fusion.3 = f32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.9
}
"""


def test_kernel_instructions_are_found_in_the_compiled_program():
    assert trace_reduce.kernel_instructions(HLO, "fused_step_pallas") == {
        "closed_call.13", "closed_call.14"}
    assert trace_reduce.kernel_instructions(HLO, "sat_aggregate") == {
        "other.2"}
    assert trace_reduce.kernel_instructions(HLO, "gru_cell") == set()
    labels = trace_reduce.op_labels(HLO)
    assert labels["closed_call.14"] == "jit(f)/while/body"
    assert "while.20" not in labels


def test_a_kernels_time_is_the_union_of_its_operations(monkeypatch):
    # the fusion (10..40) holds the custom call (12..38): counted once
    fake = {"device": {"/device:TPU:0": [
        ("%while.20 = (s32[]) while(%t)", 5, 50),
        ("%closed_call.14 = f32[4,8] fusion(%a)", 10, 40),
        ("%closed_call.13 = f32[8] custom-call(%a)", 12, 38),
        ("%closed_call.14 = f32[4,8] fusion(%a)", 60, 70)]},
        "host": [("bench.window", 0, 100)]}
    monkeypatch.setattr(trace_reduce, "load", lambda _p: fake)
    r = trace_reduce.reduce(
        "x", kernels={"fused": {"closed_call.13", "closed_call.14"}})
    assert r["kernels"]["fused"] == pytest.approx(40e-9)
    assert r["ops"]["closed_call.14"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(55e-9)
