"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device operations are the events of each device plane's ``XLA Ops`` line;
host spans are the harness's own ``bench.*`` annotations on the host plane,
on the same clock. The traced window runs from the first harness span to the
end of the last one.

- busy: the union of operation intervals inside the window, per device,
  averaged over the devices that ran anything.
- kernel time: the summed durations of the operations a kernel pattern
  matches (by operation name or any string the event carries) by class
  (``KERNELS``), which also takes the fusions whose HLO names a kernel's
  output; and the summed durations of each Pallas kernel alone (an
  operation whose HLO is a ``tpu_custom_call``), by the name of its
  ``pallas_call`` (the operation's name without its ``.N`` suffix). A reader
  of a new kernel reads its name; no class is added.
- idle gaps: the stretches of the window with no operation on the device,
  each put to the harness span that was open at its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Iterable

# kernel -> pattern over an operation's name and string stats
KERNELS = {
    "dual_gemm": re.compile(r"dual_gemm|twinquant_dual_gemm"),
    "ragged_attention": re.compile(r"ragged_attention"),
}
# a Pallas kernel: an operation whose HLO is a custom call to the TPU's
# kernel target (a fusion that only reads a kernel's output is none)
PALLAS = re.compile(r'custom_call_target="tpu_custom_call"')
HOST_SPAN = re.compile(r"^bench\.")
OPS_LINE = "XLA Ops"
# operations that only contain others (a scan's loop): busy, but not listed
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


def short_name(name: str) -> str:
    """``%ragged_attention_kernel.6 = bf16[...] custom-call(...)`` ->
    ``ragged_attention_kernel.6``: the instruction's name, without its text."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def call_name(name: str) -> str:
    """``twinquant_dual_gemm_group.29`` -> ``twinquant_dual_gemm_group``."""
    return re.sub(r"(\.\d+)+$", "", name)


@dataclasses.dataclass
class Op:
    name: str
    start: float  # seconds, trace clock
    end: float
    kernel: str = ""  # the KERNELS class the operation falls in
    pallas: bool = False  # a Pallas kernel, whether a class matches it or not


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: dict  # KERNELS class -> summed seconds
    op_s: dict  # operation name -> summed seconds
    idle_by_span: dict  # host span name -> idle seconds
    n_ops: int
    # pallas_call name -> summed seconds of its kernel
    kernel_by_name: dict = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def classify(name: str, strings: Iterable[str]) -> str:
    for kernel, pat in KERNELS.items():
        if pat.search(name) or any(pat.search(s) for s in strings):
            return kernel
    return ""


def is_pallas(name: str, strings: Iterable[str]) -> bool:
    return bool(PALLAS.search(name)) or any(PALLAS.search(s) for s in strings)


def union(intervals: list) -> list:
    """Sorted, merged (start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def summarize(device_ops: dict, spans: list) -> Summary:
    """``device_ops``: device name -> [Op]; ``spans``: [(name, start, end)]
    host spans (seconds, same clock)."""
    if not spans:
        return Summary(0.0, 0.0, {}, {}, {}, 0)
    w0 = min(s[1] for s in spans)
    w1 = max(s[2] for s in spans)
    window = w1 - w0
    busy_per_dev = []
    kernel_s: dict = defaultdict(float)
    by_name: dict = defaultdict(float)
    op_s: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    spans = sorted(spans, key=lambda s: s[1])
    n = 0
    for ops in device_ops.values():
        inside = [o for o in ops if o.end > w0 and o.start < w1]
        if not inside:
            continue
        n += len(inside)
        for o in inside:
            d = min(o.end, w1) - max(o.start, w0)
            if not CONTAINERS.match(o.name):
                op_s[f"{o.kernel}:{o.name}" if o.kernel else o.name] += d
            if o.kernel:
                kernel_s[o.kernel] += d
            if o.pallas:
                by_name[call_name(o.name)] += d
        merged = union([(max(o.start, w0), min(o.end, w1)) for o in inside])
        busy_per_dev.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle[_span_at(spans, (a + b) / 2)] += b - a
    n_dev = max(1, len(busy_per_dev))
    return Summary(
        window_s=window,
        busy_s=sum(busy_per_dev) / n_dev,
        kernel_s={k: v / n_dev for k, v in kernel_s.items()},
        op_s={k: v / n_dev for k, v in op_s.items()},
        idle_by_span={k: v / n_dev for k, v in idle.items()},
        n_ops=n,
        kernel_by_name={k: v / n_dev for k, v in by_name.items()},
    )


def _span_at(spans: list, t: float) -> str:
    name = "no harness span"
    for s in spans:
        if s[1] > t:
            break
        if s[2] >= t:
            name = s[0]
    return name


def reduce_trace(trace_dir: str) -> Summary:
    """Read the one ``.xplane.pb`` under ``trace_dir`` and summarize it."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[0])
    device_ops: dict = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    strings = [str(v) for _, v in ev.stats if isinstance(v, str)]
                    ops.append(Op(short_name(ev.name), ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  classify(ev.name, strings), is_pallas(ev.name, strings)))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return summarize(device_ops, spans)
