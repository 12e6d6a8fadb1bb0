"""Serving driver: quantize a trained model to PACKED W4A4 (the fused-kernel
format) and serve batched requests through the continuous-batching engine —
first the bucketed paged engine, then the unified RAGGED engine
(docs/serving.md): chunked prefill + decode in one launch per step, with a
token-equality check between the two.

On CPU the quantized linears run the jnp oracle path; on TPU the same params
route through the fused Pallas kernel (models/common.linear dispatch).

Run:        PYTHONPATH=src:. python examples/serve_quantized.py
CI smoke:   PYTHONPATH=src:. python examples/serve_quantized.py --smoke
(--smoke serves random-init weights — the serving path is shape-bound, so
admission/paging/ragged behavior and every assertion are identical; it just
skips the minutes of corpus training behind the cached bench model.)
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import QuantSpec
from repro.core.twinquant import fuse_params, quantize_params
from repro.launch.serve import ContinuousBatchingEngine, Request, SamplingParams


def make_requests(cfg):
    """A shared system prompt with mixed tails + mixed per-request sampling."""
    system = "# TwinQuant demo: continue the code\n"  # shared system prompt
    prompts = [
        "def main(", "import jax", "class Model", "# TwinQuant",
        "return x +", "for i in",
    ]
    requests = [
        Request(
            jnp.asarray(list((system + p).encode()), jnp.int32), max_new=12,
            sampling=(SamplingParams() if i % 2 == 0
                      else SamplingParams(temperature=0.8, top_k=40, seed=i)),
        )
        for i, p in enumerate(prompts)
    ]
    return prompts, requests


def main(smoke: bool = False):
    if smoke:
        from benchmarks.common import BENCH_CFG
        from repro.models import dense

        cfg, params = BENCH_CFG, None
        params = dense.init_params(cfg, jax.random.PRNGKey(0))
        print("smoke mode: random-init weights (shape-identical serving path)")
    else:
        from benchmarks.common import get_trained_model

        cfg, params, _ = get_trained_model()
    print("quantizing to packed W4A4 (rank 32, group 128) ...")
    qspec = QuantSpec(mode="w4a4", rank=32)
    qparams = quantize_params(params, cfg, qspec)

    n_quant = sum(1 for p in jax.tree_util.tree_leaves_with_path(qparams)
                  if getattr(p[0][-1], "key", None) == "rp")
    # default serving config: merge sibling packs (q/k/v, gate/up) so each
    # group runs as ONE fused launch (checkpoints stay unfused on disk)
    qparams = fuse_params(qparams)
    pb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)) / 1e6
    qb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(qparams)) / 1e6
    print(f" {n_quant} linears packed; params {pb:.1f}MB -> {qb:.1f}MB")

    # paged serving runtime (DESIGN.md §14): global page pool + per-slot
    # block tables, admission gated on free pages, shared prompt prefixes
    # served from the prefix cache (paged=False is the dense A/B oracle)
    engine = ContinuousBatchingEngine(cfg, qparams, batch_slots=4, max_len=96,
                                      paged=True, page_size=8)
    prompts, requests = make_requests(cfg)
    t0 = time.monotonic()
    engine.serve(requests)
    dt = time.monotonic() - t0
    for p, r in zip(prompts, requests):
        txt = bytes(t for t in r.out if t < 256).decode(errors="replace")
        mode = "greedy" if r.sampling.temperature <= 0 else "t=0.8/k=40"
        print(f"  [{mode:>10}] {p!r} -> {txt!r}")
    th = engine.throughput()
    total_new = sum(len(r.out) for r in requests)
    dev = jax.devices()[0]
    print(f" served {len(requests)} requests, {total_new} tokens in {dt:.1f}s: "
          f"decode {th['decode_tok_s']:.1f} tok/s, prefill {th['prefill_tok_s']:.1f} tok/s, "
          f"mean occupancy {th['mean_batch_occupancy']:.2f}/{engine.batch} slots "
          f"(on {dev.platform} {dev.device_kind})")
    # which kernel schedule each quantized linear routed to, per trace:
    # decode steps (M=slots<=8) must hit the decode-shaped schedule, the
    # prompt prefill (M=prompt length) the prefill one
    routes = ", ".join(f"{k}:{v}" for k, v in sorted(th["routing"].items()))
    print(f" dispatch routes: {routes}")
    mem = engine.memory()
    cs = engine.compile_stats()
    print(f" paging: {mem['pages_peak']}/{mem['n_pages']} pages peak "
          f"({mem['peak_cache_bytes'] / 1e3:.0f}kB vs dense "
          f"{mem['dense_cache_bytes'] / 1e3:.0f}kB), "
          f"prefix hits {th['prefix_hits']}/{th['prefix_lookups']} "
          f"({th['prefix_hit_tokens']} prompt tokens served from cache), "
          f"{cs['prefill_traces']} prefill traces for buckets {cs['prefill_buckets']}")
    engine.check_page_invariants()
    assert th["routing"].get("dual/decode", 0) > 0, "decode steps must route decode"
    assert th["routing"].get("dual_fused/decode", 0) > 0, \
        "fused serving must route the fused decode kind (q/k/v, gate/up)"
    assert th["prefix_hits"] > 0, "shared system prompt must hit the prefix cache"

    # --- the unified RAGGED engine (docs/serving.md): every step is ONE
    # launch over a flat token batch — decode rows first, prompt chunks fill
    # the remaining token budget — compiling a single executable instead of
    # the prefill bucket set. Token equality vs the bucketed engine is exact
    # when the two runs split work identically: prefix caching off on BOTH
    # (ragged matches full prefixes, bucketed matches power-of-two lengths)
    # and a budget wide enough that each prompt prefills in one chunk (a
    # chunk boundary reassociates the f32 softmax accumulation — ~1e-7,
    # enough to flip a near-tied argmax; tests/test_ragged_engine.py covers
    # the chunked regime).
    _, oreqs = make_requests(cfg)
    oracle = ContinuousBatchingEngine(cfg, qparams, batch_slots=4, max_len=96,
                                      paged=True, page_size=8,
                                      prefix_caching=False)
    oracle.serve(oreqs)
    ragged = ContinuousBatchingEngine(cfg, qparams, batch_slots=4, max_len=96,
                                      paged=True, page_size=8,
                                      prefix_caching=False,
                                      ragged=True, token_budget=192)
    _, rreqs = make_requests(cfg)
    t0 = time.monotonic()
    ragged.serve(rreqs)
    dt = time.monotonic() - t0
    rth = ragged.throughput()
    rcs = ragged.compile_stats()
    rroutes = ", ".join(f"{k}:{v}" for k, v in sorted(rth["routing"].items())
                        if k.startswith("ragged/"))
    print(f" ragged engine: {sum(len(r.out) for r in rreqs)} tokens in {dt:.1f}s, "
          f"decode {rth['decode_tok_s']:.1f} tok/s; "
          f"{rcs['ragged_traces']} ragged executable(s), "
          f"{rcs['prefill_traces']} prefill buckets; attention routes: {rroutes}")
    ragged.check_page_invariants()
    assert [r.out for r in rreqs] == [r.out for r in oreqs], \
        "ragged serving must be token-identical to the bucketed engine"
    assert rcs["ragged_traces"] == 1 and rcs["prefill_traces"] == 0, rcs
    print("serve_quantized OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="serve random-init weights (CI example-smoke; skips "
                         "the cached trained bench model)")
    main(**vars(ap.parse_args()))
