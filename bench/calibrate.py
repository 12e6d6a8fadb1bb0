#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 [--control] [--dump F]

For each seed it runs the cell as ``bench/run.py`` does (the process keeps
its compiled programs between seeds) and prints one JSON line: the numbers
compared with their limits and whether the run is correct, and with
``--control`` the same judgement, through the same comparison, of the
tokens that the lower-precision control (the reference in float8_e4m3fn
where the configuration states bfloat16) puts first over the same sample.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import cell as cellmod  # noqa: E402
from run import enable_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dump", help="append each seed's per-position gaps (JSON lines) here")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, JAX reports {dev.platform!r}", file=sys.stderr)
        return 3
    enable_cache(BENCH.parent)
    spec = cellmod.load(BENCH.parent, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        res = cellmod.run(spec, seed, args.seconds, False, dev, t_start=t0,
                          control=args.control)
        for line in res.pop("_stderr"):
            print(line, file=sys.stderr, flush=True)
        gaps = res["control"].pop("gaps") if args.control else None
        print(json.dumps({"workload": args.workload, "seed": seed, **res}), flush=True)
        if args.dump and gaps is not None:
            with open(args.dump, "a") as f:
                f.write(json.dumps({"seed": seed, "served": res["control"]["served"],
                                    **gaps}) + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
