"""One cell, end to end: set-up, window, late first tokens, metrics, check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, the architecture that file names
(``bench/architectures/<name>.py``), its traffic file, and one reader per
metric (``bench/end_to_end/<metric>.py``, ``bench/layer_metrics/<metric>.py``).
Adding an architecture, a configuration, a mix or a metric adds files and
entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

from harness import check as checkmod
from harness import load as loadmod
from harness.driver import Driver
from harness.traffic import make_generator


@dataclasses.dataclass
class Spec:
    root: Path
    name: str
    cell: dict
    conf: dict
    mix: dict
    end_to_end: list
    per_layer: list
    architecture: object  # the module bench/architectures/<conf["architecture"]>.py


def _bench_dir(root: Path) -> Path:
    return root / "bench"


def load(root: Path, workload: str) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_file = confs[cell["config"]]["file"]
    conf = json.loads((root / conf_file).read_text())
    if "architecture" not in conf:
        raise ValueError(f'configuration {conf_file} has no "architecture" key: it names '
                         f"the module bench/architectures/<architecture>.py")
    architecture = loadmod.module(root, "architectures", conf["architecture"])
    mix = json.loads((_bench_dir(root) / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    pl = [m for m in bench["per_layer"]
          if workload in m.get("workloads", ())
          or ("workloads" not in m and m["moves"] in names)]
    return Spec(root, workload, cell, conf, mix, e2e, pl, architecture)


def reader(root: Path, kind: str, metric: str):
    """The ``read(ctx)`` function of ``bench/<kind>/<metric>.py``, where
    kind is ``end_to_end`` or ``layer_metrics``."""
    return loadmod.module(root, kind, metric).read


class CompileClock:
    """Wall times at which JAX finished a backend compile (XLA or Mosaic)."""

    def __init__(self):
        self.events: list = []

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), duration, kw.get("fun_name", "")))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, *_ in self.events if t0 <= t <= t1)

    def names(self, t0: float, t1: float) -> list:
        return [n for t, _, n in self.events if t0 <= t <= t1]

    def seconds(self) -> float:
        return sum(d for _, d, _ in self.events)


_CLOCK: Optional[CompileClock] = None


def compile_clock() -> CompileClock:
    global _CLOCK
    if _CLOCK is None:
        import jax

        _CLOCK = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(_CLOCK)
    return _CLOCK


class GcClock:
    """Each of Python's garbage collections: (end time, generation, seconds)."""

    def __init__(self):
        self.events: list = []
        self._t0 = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.events.append((time.monotonic(), info["generation"],
                                time.monotonic() - self._t0))

    def report(self, t0: float, t1: float) -> str:
        ev = [(g, d) for t, g, d in self.events if t0 <= t <= t1]
        full = [d for g, d in ev if g == 2]
        return (f"gc in the window: {len(ev)} collections, {len(full)} of generation 2"
                f" (longest {1e3 * max(full, default=0.0):.1f} ms), "
                f"{1e3 * sum(d for _, d in ev):.1f} ms in all")


def longest_step(records: list, t0: float, t1: float) -> str:
    """The longest engine step in ``[t0, t1]`` and its longest phase: a stall
    shows here as one step many times the others."""
    recs = [r for r in records if t0 <= r.t0 and r.t1 <= t1]
    if not recs:
        return "no engine step in the window"
    dur = sorted(r.t1 - r.t0 for r in recs)
    r = max(recs, key=lambda r: r.t1 - r.t0)
    name, sec = max(r.phases.items(), key=lambda kv: kv[1], default=("none", 0.0))
    return (f"engine steps in the window: median {1e3 * dur[len(dur) // 2]:.2f} ms, "
            f"longest {1e3 * (r.t1 - r.t0):.2f} ms ({name} {1e3 * sec:.2f} ms, "
            f"{r.t0 - t0:.1f} s into the window), {sum(d > 0.25 for d in dur)} over 250 ms")


def pool_pages(kv_bytes_per_token: int, eng: dict) -> tuple:
    """Pages the pool gets: every slot's full timeline, so the pool never
    bounds admission in these cells. Returns (n_pages, page_bytes)."""
    page_bytes = kv_bytes_per_token * eng["page_size"]
    return eng["batch_slots"] * -(-eng["max_len"] // eng["page_size"]), page_bytes


def run(spec: Spec, seed: int, seconds: float, trace: bool, dev, *, t_start: float,
        faults=None, control: bool = False) -> dict:
    """Run one cell on ``dev``; returns the result object (see bench/run.py).
    ``faults`` (tests only) breaks the engine after it is built; ``control``
    (bench/calibrate.py) also judges the lower-precision control on the
    same sample, under ``control``."""
    import jax

    from harness import program

    log: list = []
    clock = compile_clock()
    gc_clock = GcClock()
    mix, eng_conf = spec.mix, spec.mix["engine"]
    A = spec.architecture
    cfg = program.model_config(spec.conf)
    arch = A.arch_of(cfg)

    t0 = time.monotonic()
    params = program.fuse(A.build_params(arch, seed), dev)
    jax.block_until_ready(params)
    log.append(f"weights {time.monotonic() - t0:.3f} s")
    n_pages, page_bytes = pool_pages(A.kv_bytes_per_token(arch), eng_conf)
    engine = program.build_engine(cfg, params, mix, n_pages)
    del params
    ms = dev.memory_stats() or {}
    if "bytes_limit" in ms:
        log.append(f"pool {n_pages} pages of {page_bytes} B; HBM in use "
                   f"{ms['bytes_in_use'] / ms['bytes_limit']:.4f} of {ms['bytes_limit']} B "
                   f"after the engine is built")
    if faults is not None:
        faults(engine)
    gen = make_generator(mix, seed, cfg.vocab)
    warm_s, warm_cap_s = float(mix["warm_s"]), float(mix["warm_cap_s"])
    trace_s, drain_s = float(mix["trace_s"]), float(mix["drain_s"])
    if hasattr(gen, "horizon"):
        gen.horizon = warm_s + seconds + drain_s + warm_cap_s
    drv = Driver(engine, gen, annotate=trace)

    # set-up: the traffic's warm period, then on until a request has
    # finished (its release compiles the engine's exit-path updates) and, for
    # a stationary start, until no slot is still prefilling its first prompt
    drv.start()
    t_warm = time.monotonic()
    drv.run_until(t_warm + warm_s)

    def warm():
        if not any(s.finished is not None for s in drv.sent):
            return False
        return not (mix.get("warm_until_decoding")
                    and any(s is not None and s.status == "PREFILL" for s in engine.slots))

    drv.run_until(t_warm + warm_cap_s, stop=warm)
    w0 = time.monotonic()
    setup_s = w0 - t_start
    log.append(f"set-up {setup_s:.3f} s (warm traffic {w0 - t_warm:.3f} s); "
               f"backend compile {clock.seconds():.3f} s")

    trace_dir = None
    q_open = len(engine.queue)
    mark0 = drv.mark()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        drv.run_until(w0 + min(trace_s, seconds))
        jax.block_until_ready(engine.state)
        mark1 = drv.mark()
        jax.profiler.stop_trace()
    w1 = w0 + seconds
    drv.run_until(w1)
    w1 = max(w1, time.monotonic())
    log.append(f"queue depth {q_open} at the open, {len(engine.queue)} at the close; "
               f"{sum(s is not None for s in engine.slots)} slots busy")
    if not trace:
        mark1 = drv.mark()
    d = {k: mark1[1][k] - mark0[1][k] for k in ("decode_steps", "decode_tokens", "prefill_tokens")}
    log.append(f"window: {d['decode_steps']} launches, {d['decode_tokens']} decode rows, "
               f"{d['prefill_tokens']} prefill rows")
    log.append(longest_step(engine.step_records, w0, w1))
    log.append(gc_clock.report(w0, w1))
    gc.callbacks.remove(gc_clock)
    window_compiles = clock.between(w0, w1)
    if window_compiles:
        log.append(f"compiled in the window: {clock.names(w0, w1)}")

    # late first tokens of requests due in the window, then the censor time
    def all_first():
        return all(s.request.t_first_token is not None or s.request.done
                   for s in drv.sent if w0 <= s.due < w1)

    drv.run_until(w1 + drain_s, stop=all_first)
    censor = time.monotonic()

    ms = dev.memory_stats() or {}
    routes = engine.routing()
    failed = sum(1 for s in drv.sent if s.request.status in ("FAILED", "TIMED_OUT", "CANCELLED"))
    in_window = [s for s in drv.sent if w0 <= s.due < w1]
    result = {"correct": False, "attempted": len(in_window), "failed": failed}

    from harness.context import Context

    metrics = {}
    if trace:
        from harness.trace import reduce_trace

        tr = reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(spec=spec, arch=arch, driver=drv, device_kind=dev.device_kind,
                      t0=mark0[0], t1=mark1[0], stats0=mark0[1], stats1=mark1[1],
                      architecture=A, trace=tr)
        wanted, kind = spec.per_layer, "layer_metrics"
        result["breakdown"] = tr.breakdown()
    else:
        ctx = Context(spec=spec, arch=arch, driver=drv, device_kind=dev.device_kind,
                      t0=w0, t1=w1, stats0=mark0[1], stats1=mark1[1],
                      architecture=A, censor=censor, setup_s=setup_s)
        wanted, kind = spec.end_to_end, "end_to_end"
    for m in wanted:
        v = reader(spec.root, kind, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": int(ms.get("peak_bytes_in_use", 0))}
    if trace:
        result["device"]["busy_s"] = tr.busy_s
        result["device"]["window_s"] = tr.window_s

    sample = checkmod.sample(drv.sent, seed, mix.get("check", {}))
    del engine, drv.engine
    gc.collect()
    t_ref = time.monotonic()
    ref = loadmod.module(spec.root, "references", spec.conf["reference"])
    rd = checkmod.readings(ref, arch, seed, sample, control) if sample else {}
    judged = checkmod.compare(spec.conf, sample, rd, control)
    compared = judged["program"]
    log.append(f"reference over {len(sample)} requests "
               f"({sum(len(s.request.out) for s in sample)} served tokens): "
               f"{time.monotonic() - t_ref:.3f} s")
    compared["ref_routes"] = {"value": sum(v for k, v in routes.items() if "/ref" in k),
                              "limit": 0}
    compared["window_compiles"] = {"value": window_compiles, "limit": 0}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    result["correct"] = checkmod.correct(compared)
    if control:
        result["control"] = {"correct": checkmod.correct(judged["control"]),
                             "compared": judged["control"],
                             "served": [len(s.request.out) for s in sample],
                             "gaps": {k: v.tolist() for k, v in rd.items()}}
    result["compared"] = compared
    log.append(f"routes {json.dumps(routes, sort_keys=True)}")
    log.extend(f"compared {k} {c['value']} limit {c['limit']}" for k, c in compared.items())
    result["_stderr"] = log
    return result

