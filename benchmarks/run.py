"""Benchmark harness entrypoint: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (run.py contract). JSON
artifacts land in artifacts/ for EXPERIMENTS.md.

  bench_accuracy   — Tables 1/3: PPL under fp16/naive/+lowrank/+hadamard/TwinQuant
  bench_rank       — Table 2 / Fig 6: rank sensitivity + overhead
  bench_kernels    — Tables 6/7: fused dual-component kernel (derived + exactness)
  bench_throughput — Figure 5: end-to-end W4A4 vs FP16 speedup (derived)
  bench_error_analysis — Figs 1/2/7 + Thm 4.1 gains
  bench_roofline   — §Roofline table from dry-run artifacts

``--quick`` is the CI bench lane: the small-shape interpret-mode kernel
checks plus the measured serving-engine throughput sweep (no model
training), with the combined results written to ``--out`` (BENCH_PR.json)
for benchmarks/compare.py to gate against benchmarks/baseline.json.

``--fused`` (default) / ``--no-fused`` toggles horizontal projection fusion
(q/k/v and gate/up as one launch) for the throughput sweep; CI uploads one
artifact per setting so the fusion speedup is visible in the artifact trail.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

QUICK_MODULES = ("kernels", "throughput")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modules", nargs="*", help="subset of benchmark modules to run")
    ap.add_argument(
        "--quick", action="store_true",
        help="CI bench lane: kernels + serving-engine throughput only",
    )
    ap.add_argument(
        "--fused", dest="fused", action="store_true", default=True,
        help="fuse sibling projections (q/k/v, gate/up) into one launch (default)",
    )
    ap.add_argument(
        "--no-fused", dest="fused", action="store_false",
        help="A/B lane: per-sibling launches (the pre-fusion serving path)",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="add the paged-serving lane (mixed-prompt + shared-prefix "
             "workload, paged vs dense engines) to the throughput module — "
             "the BENCH_PAGED.json artifact",
    )
    ap.add_argument(
        "--burst", action="store_true",
        help="add the ragged burst lane (steady decoders + long-prompt "
             "admission through the unified ragged step) to the throughput "
             "module — the BENCH_BURST.json artifact",
    )
    ap.add_argument(
        "--spec", action="store_true",
        help="add the speculative-decoding lane (self-drafted multi-token "
             "verification through the in-kernel paged decode attention, "
             "spec vs plain engines) to the throughput module — the "
             "BENCH_SPEC.json artifact",
    )
    ap.add_argument(
        "--slo", action="store_true",
        help="add the trace-driven SLO lane (seeded production workload "
             "through the ragged preemptive engine: TTFT/TPOT percentiles, "
             "goodput under SLO, solo-oracle token equality, knob sweep) to "
             "the throughput module — the BENCH_SLO.json artifact",
    )
    ap.add_argument("--out", default=None, help="write combined results JSON here")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_accuracy,
        bench_error_analysis,
        bench_kernels,
        bench_rank,
        bench_roofline,
        bench_throughput,
    )

    mods = {
        "kernels": bench_kernels,
        "throughput": bench_throughput,
        "error_analysis": bench_error_analysis,
        "accuracy": bench_accuracy,
        "rank": bench_rank,
        "roofline": bench_roofline,
    }
    if args.quick:
        selected = list(QUICK_MODULES)
    else:
        selected = args.modules or list(mods)
    print("name,us_per_call,derived")
    results, failed = {}, []
    for name in selected:
        try:
            if name == "throughput":
                results[name] = mods[name].run(quick=args.quick, fused=args.fused,
                                               paged=args.paged, burst=args.burst,
                                               spec=args.spec, slo=args.slo)
            elif name in QUICK_MODULES:
                results[name] = mods[name].run(quick=args.quick)
            else:
                results[name] = mods[name].run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if args.out:
        doc = {"schema": 1, "quick": args.quick, "fused": args.fused, "results": results}
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
