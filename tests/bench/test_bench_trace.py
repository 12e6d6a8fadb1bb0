"""Trace reduction: busy and idle time, kernel time (by class and by
pallas_call name) and the host span behind each idle gap, on a synthetic
trace and on one recorded here."""

import jax
import jax.numpy as jnp
import pytest

import bench_testlib  # noqa: F401  (puts bench/ on the path)
from harness import trace as T


def names_add_up(s: T.Summary) -> None:
    """The device time by pallas_call name sums, over the names of each
    class, to that class's kernel time, where every operation of the class
    is a kernel."""
    by_class: dict = {}
    for name, sec in s.kernel_by_name.items():
        cls = T.classify(name, [])
        by_class[cls] = by_class.get(cls, 0.0) + sec
    by_class.pop("", None)
    assert by_class == pytest.approx(s.kernel_s)
    assert set(by_class) == set(s.kernel_s)


def test_synthetic_trace_shares():
    ops = {"/device:TPU:0": [
        # kernels as the chip names them: the pallas_call's name, numbered
        T.Op("twinquant_dual_gemm.1", 1.0, 3.0, "dual_gemm", True),
        T.Op("fusion.2", 2.5, 4.0),  # overlaps the kernel: counted once
        T.Op("ragged_attention.7", 6.0, 7.0, "ragged_attention", True),
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
    assert b["device_ops"][0] == ["dual_gemm:twinquant_dual_gemm.1", pytest.approx(2.0)]
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(3.5)]
    assert s.kernel_by_name == pytest.approx({"twinquant_dual_gemm": 2.0,
                                              "ragged_attention": 1.0})
    names_add_up(s)


def test_device_time_by_pallas_call_name():
    ops = {"/device:TPU:0": [
        T.Op("twinquant_dual_gemm_group.29", 1.0, 2.0, "dual_gemm", True),
        T.Op("twinquant_dual_gemm.29", 2.0, 2.5, "dual_gemm", True),
        T.Op("twinquant_dual_gemm_group.28", 2.5, 3.5, "dual_gemm", True),
        T.Op("ragged_attention.14", 4.0, 6.0, "ragged_attention", True),
        T.Op("expert_gemm.3", 6.0, 6.25, "", True),  # a kernel no class names
        T.Op("fusion.42", 6.5, 7.0),  # not a kernel
    ]}
    s = T.summarize(ops, [("bench.step", 0.0, 8.0)])
    assert s.kernel_by_name == pytest.approx({
        "twinquant_dual_gemm_group": 2.0, "twinquant_dual_gemm": 0.5,
        "ragged_attention": 2.0, "expert_gemm": 0.25})
    assert s.kernel_s == pytest.approx({"dual_gemm": 2.5, "ragged_attention": 2.0})
    names_add_up(s)
    # a fusion that reads a kernel's output falls in its class (as since the
    # class was made), and in no kernel's time by name
    ops["/device:TPU:0"].append(T.Op("bitcast_add_fusion.2", 7.0, 7.5, "dual_gemm"))
    s = T.summarize(ops, [("bench.step", 0.0, 8.0)])
    assert s.kernel_s["dual_gemm"] == pytest.approx(3.0)
    assert "bitcast_add_fusion" not in s.kernel_by_name


def test_kernel_patterns():
    assert T.classify("custom-call.3", ["jit(step)/_dual_gemm_kernel"]) == "dual_gemm"
    assert T.classify("ragged_attention_kernel", []) == "ragged_attention"
    assert T.classify("fusion.9", ["jit(step)/add"]) == ""
    # a Pallas kernel is known by its HLO, whatever it computes
    hlo = ('%probe_kernel.1 = f32[256,256]{1,0} custom-call(%x.1), '
           'custom_call_target="tpu_custom_call"')
    assert T.is_pallas(hlo, [])
    assert T.is_pallas("probe_kernel.1", [hlo])
    fusion = ("%fusion = f32[256,256]{1,0} fusion(f32[256,256]{1,0} %x.1, "
              "f32[256,256]{1,0} %probe_kernel.1), kind=kOutput")
    assert not T.is_pallas(fusion, ["jit(f)/probe_kernel/pallas_call"])
    assert T.call_name(T.short_name(hlo)) == "probe_kernel"


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
    names_add_up(s)


def test_operation_names_and_containers():
    text = "%ragged_attention_kernel.6 = bf16[256,3072]{1,0} custom-call(s32[32,320] %a)"
    assert T.short_name(text) == "ragged_attention_kernel.6"
    ops = {"/device:TPU:0": [T.Op("while.3", 0.0, 4.0), T.Op("fusion.1", 1.0, 2.0)]}
    s = T.summarize(ops, [("bench.step", 0.0, 4.0)])
    assert s.busy_s == pytest.approx(4.0)  # the loop runs on the device throughout
    assert s.op_s == pytest.approx({"fusion.1": 1.0})  # only leaf operations listed
