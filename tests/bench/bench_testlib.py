"""Helpers for the benchmark's tests: a checkout in a temporary directory
with the benchmark's files, the program's sources and tiny cells that run on
the CPU in seconds."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def architecture(name: str):
    """The module ``bench/architectures/<name>.py`` of this repository."""
    from harness import load

    return load.module(REPO, "architectures", name)


# Widths are multiples of 256, so every linear is packed (W4A4) as at full
# size; depth, heads and vocabulary are cut so a run takes seconds.
TINY = {"n_layers": 2, "d_model": 256, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 128, "d_ff": 512, "vocab": 512}


# Limits of the trimmed mean logit gap (``logit_gap_trim90``) for the tiny
# variants on the CPU, where the program's attention runs its jnp oracle (bf16
# probabilities on decode rows) and so departs from the f32 reference more
# than the chip's kernel does. CPU readings over seeds 1-6, 2**31 + 11 and
# 2**31 + 12 (2 layers, about 200 served tokens each): qwen2 program
# 0.004-0.025, control 0.10-0.23; phi4-mini program 0.024-0.049, control
# 0.26-0.38.
TINY_LIMITS = {"qwen2-1.5b": 0.05, "phi4-mini-3.8b": 0.12}


def tiny_config(registered: str, name: str) -> dict:
    conf = json.loads((BENCH / "configs" / f"{registered}.json").read_text())
    conf["name"] = name
    conf["overrides"] = {**conf.get("overrides", {}), **TINY}
    conf["check"] = {"min_tokens": 16, "logit_gap_trim90": TINY_LIMITS[registered]}
    return conf


def tiny_mix(loop: str = "closed") -> dict:
    mix = {
        "loop": loop,
        "prompt": {"dist": "lognormal", "median": 32, "sigma": 0.5, "min": 16, "max": 64},
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 24},
        "engine": {"batch_slots": 4, "max_len": 192, "page_size": 16, "token_budget": 32,
                   "prefix_caching": True},
        "warm_s": 0.5,
        "warm_cap_s": 60.0,
        "trace_s": 1.0,
        "drain_s": 5.0,
        "check": {"tokens": 200, "max_requests": 16},
    }
    if loop == "closed":
        mix["closed"] = {"clients": 4, "think_s": 0.0, "stationary_start": True}
        mix["warm_until_decoding"] = True
    else:
        mix["sessions"] = {"rate_per_s": 4.0, "system_prompts": 2, "system_len": 32,
                           "zipf_s": 1.0, "turns_min": 2, "turns_max": 3,
                           "think_mean_s": 0.2}
    return mix


# Metrics whose readers are here for a cell that BENCHMARK.json does not hold
# yet (``qwen2.chat_sessions``, PERF.md Open questions); the tiny cells report
# them as well.
PENDING = {
    "end_to_end": [{"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
                    "bound": 0.15, "source": "host_clock", "workloads": []}],
    "per_layer": [{"name": "prefix.hit_share", "unit": "%", "better": "higher",
                   "source": "program_counter", "layer": "prefix cache",
                   "moves": "ttft_p90_ms", "workloads": []}],
}


def make_root(tmp: Path, cells: list, configs: dict, mixes: dict,
              metrics: dict = None, files: dict = None) -> Path:
    """A checkout at ``tmp``: the benchmark's files, a link to the program,
    and a BENCHMARK.json naming ``cells`` [(name, config, mix)], with the
    given configuration and traffic files added. ``metrics`` maps a new
    per-layer metric name to its reader's source; ``files`` maps a path
    under ``bench/`` to a file to copy there, which must be new."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, src in (files or {}).items():
        dst = root / "bench" / rel
        if dst.exists():
            raise FileExistsError(f"bench/{rel} is not a new file")
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for kind, pending in PENDING.items():
        have = {m["name"] for m in bench[kind]}
        bench[kind] += [dict(m) for m in pending if m["name"] not in have]
    for name, conf in configs.items():
        path = f"bench/configs/{name}.json"
        (root / path).write_text(json.dumps(conf))
        bench["configs"].append({"name": name, "source": conf["source"], "file": path,
                                 "reduced": ["num_hidden_layers"], "why": "test"})
    for name, mix in mixes.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    names = [c[0] for c in cells]
    for name, config, mix in cells:
        bench["workloads"].append({"name": name, "config": config, "traffic": mix,
                                   "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + names
    for name, src in (metrics or {}).items():
        (root / "bench" / "layer_metrics" / f"{name}.py").write_text(src)
        bench["per_layer"].append({"name": name, "unit": "%", "better": "higher",
                                   "source": "program_counter", "layer": "engine",
                                   "moves": "itl_p95_ms", "workloads": names})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             faults=None, control: bool = False) -> dict:
    """Drive a whole run on the CPU, past the harness's look for a chip."""
    import jax

    from harness import cell

    spec = cell.load(root, workload)
    res = cell.run(spec, seed, seconds, trace, jax.devices()[0],
                   t_start=time.monotonic(), faults=faults, control=control)
    res.pop("_stderr")
    return res
