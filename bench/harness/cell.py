"""One cell, end to end: set-up, window, late first tokens, metrics, check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, its traffic file, and one reader per per-layer
metric in ``bench/layer_metrics/<metric>.py``. Adding a configuration, a mix
or a metric adds files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from harness import check as checkmod
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


def _bench_dir(root: Path) -> Path:
    return root / "bench"


def load(root: Path, workload: str) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    mix = json.loads((_bench_dir(root) / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    pl = [m for m in bench["per_layer"]
          if workload in m.get("workloads", ())
          or ("workloads" not in m and m["moves"] in names)]
    return Spec(root, workload, cell, conf, mix, e2e, pl)


def reader(root: Path, kind: str, metric: str):
    """The ``read(ctx)`` function of ``bench/<kind>/<metric>.py``, where
    kind is ``end_to_end`` or ``layer_metrics``."""
    path = _bench_dir(root) / kind / f"{metric}.py"
    mod_name = f"bench_{kind}_{metric}".replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


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


class LaunchLog:
    """Host copies of each ragged launch's row metadata (slot, pos, ctx,
    logit rows), so the cost functions can count the rows that carry a
    token. It wraps the engine's jitted step in every run, traced or not, so
    both kinds of run drive the same path; it records only while ``on``.
    The program has no counter for this yet (PERF.md, Open questions)."""

    def __init__(self, engine):
        self.engine = engine
        self.fn = engine._ragged
        self.records: list = []
        self.on = False

    def __call__(self, params, state, tokens, slot, pos, ctx, logit_idx):
        out = self.fn(params, state, tokens, slot, pos, ctx, logit_idx)
        if self.on:
            self.records.append((time.monotonic(), slot, pos, ctx, logit_idx))
        return out

    def install(self) -> "LaunchLog":
        self.engine._ragged = self
        return self

    def host(self) -> list:
        return [(t, *(np.asarray(a) for a in arrays)) for t, *arrays in self.records]


def pool_pages(cfg, eng: dict) -> tuple:
    """Pages the pool gets: every slot's full timeline, so the pool never
    bounds admission in these cells. Returns (n_pages, page_bytes)."""
    page_bytes = cfg.n_layers * eng["page_size"] * cfg.n_kv_heads * cfg.head_dim * 2 * 2
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
    mix, eng_conf = spec.mix, spec.mix["engine"]
    cfg = program.model_config(spec.conf)
    arch = program.arch_of(cfg)

    t0 = time.monotonic()
    params = program.fuse(program.build_params(cfg, seed), dev)
    jax.block_until_ready(params)
    log.append(f"weights {time.monotonic() - t0:.3f} s")
    n_pages, page_bytes = pool_pages(cfg, eng_conf)
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
    launches = LaunchLog(engine).install()

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
        launches.on = True
        jax.profiler.start_trace(trace_dir)
        drv.run_until(w0 + min(trace_s, seconds))
        jax.block_until_ready(engine.state)
        mark1 = drv.mark()
        jax.profiler.stop_trace()
        launches.on = False
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
                      launches=launches.host(), trace=tr)
        wanted, kind = spec.per_layer, "layer_metrics"
        result["breakdown"] = tr.breakdown()
    else:
        ctx = Context(spec=spec, arch=arch, driver=drv, device_kind=dev.device_kind,
                      t0=w0, t1=w1, stats0=mark0[1], stats1=mark1[1],
                      censor=censor, setup_s=setup_s)
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
    del engine, drv.engine, launches
    gc.collect()
    t_ref = time.monotonic()
    rd = checkmod.readings(spec.conf, arch, seed, sample, control) if sample else {}
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

