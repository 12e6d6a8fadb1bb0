"""Trace reduction: busy and idle time, kernel time and the host span behind
each idle gap, on a synthetic trace and on one recorded here."""

import jax
import jax.numpy as jnp
import pytest

import bench_testlib  # noqa: F401  (puts bench/ on the path)
from harness import trace as T


def test_synthetic_trace_shares():
    ops = {"/device:TPU:0": [
        T.Op("custom-call.1", 1.0, 3.0, "dual_gemm"),
        T.Op("fusion.2", 2.5, 4.0),  # overlaps the kernel: counted once
        T.Op("custom-call.7", 6.0, 7.0, "ragged_attention"),
        T.Op("fusion.2", 9.5, 11.0),  # half outside the window
    ]}
    spans = [("bench.step", 0.0, 4.5), ("bench.wait_arrival", 4.5, 6.0),
             ("bench.step", 6.0, 10.0)]
    s = T.summarize(ops, spans)
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(3.0 + 1.0 + 0.5)
    assert s.idle_share == pytest.approx(0.55)
    assert s.kernel_s == pytest.approx({"dual_gemm": 2.0, "ragged_attention": 1.0})
    # idle: [0,1) step, [4,6) mostly waiting (middle at 5), [7,9.5) step
    assert s.idle_by_span == pytest.approx({"bench.step": 1.0 + 2.5, "bench.wait_arrival": 2.0})
    b = s.breakdown()
    assert b["device_ops"][0] == ["dual_gemm:custom-call.1", pytest.approx(2.0)]
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(3.5)]


def test_kernel_patterns():
    assert T.classify("custom-call.3", ["jit(step)/_dual_gemm_kernel"]) == "dual_gemm"
    assert T.classify("ragged_attention_kernel", []) == "ragged_attention"
    assert T.classify("fusion.9", ["jit(step)/add"]) == ""


def test_recorded_trace_finds_the_harness_spans(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in ("bench.step", "bench.wait_arrival"):
        with jax.profiler.TraceAnnotation(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = T.reduce_trace(str(tmp_path))
    assert s.window_s > 0
    assert 0.0 <= s.busy_s <= s.window_s


def test_operation_names_and_containers():
    text = "%ragged_attention_kernel.6 = bf16[256,3072]{1,0} custom-call(s32[32,320] %a)"
    assert T.short_name(text) == "ragged_attention_kernel.6"
    ops = {"/device:TPU:0": [T.Op("while.3", 0.0, 4.0), T.Op("fusion.1", 1.0, 2.0)]}
    s = T.summarize(ops, [("bench.step", 0.0, 4.0)])
    assert s.busy_s == pytest.approx(4.0)  # the loop runs on the device throughout
    assert s.op_s == pytest.approx({"fusion.1": 1.0})  # only leaf operations listed
