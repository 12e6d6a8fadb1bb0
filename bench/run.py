#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``. The cell names a configuration
(``bench/configs/<config>.json``, which names its architecture,
``bench/architectures/<name>.py``) and a traffic mix
(``bench/traffic/<mix>.json``); per-layer metrics are read by
``bench/layer_metrics/<metric>.py``. One process per run, on the machine's
first chip. Without a TPU the run exits with code 3 and prints no result.

Phases: build the W4A4 weights on the chip from the seed; build the paged
ragged engine; run the cell's traffic through its warm period (set-up ends
when the window opens); measure for ``--seconds``; wait for late first
tokens; free the program's state; check a seeded sample of finished requests
against the plain reference. With ``--trace 1`` the profiler traces the
first ``trace_s`` seconds of the window and the result carries the cell's
per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import cell as cellmod  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    cached however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    root = BENCH.parent
    try:
        spec = cellmod.load(root, args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({src / 'repro'})",
              file=sys.stderr)
        return 4
    sys.path.insert(0, str(src))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: needs a TPU, JAX reports platform {dev.platform!r}", file=sys.stderr)
        return 3
    if len(devices) < spec.cell["chips"]:
        print(f"bench: cell {spec.name} needs {spec.cell['chips']} chips, "
              f"JAX reports {len(devices)}", file=sys.stderr)
        return 3
    cache = enable_cache(root)
    print(f"bench: device {dev.device_kind} x{len(devices)}, compile cache {cache}",
          file=sys.stderr, flush=True)
    result = cellmod.run(spec, args.seed, args.seconds, bool(args.trace), dev,
                         t_start=T_START)
    gc.collect()
    for line in result.pop("_stderr"):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
