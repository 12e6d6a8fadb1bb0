"""Bring-up smoke test: LLaMA3-8B-width W4A4 served through the routed Pallas
kernels on one TPU.

Run from anywhere, with the repository's ``src/`` next to this file:

    python3 chip_smoke.py

Everything runs in this one process, in five phases; the first failed check
raises and the exit code is non-zero.

(a) Device check. JAX must report a TPU. On any other platform the script
    exits with code 1 before building anything: it never falls back.
(b) Quantize. LLaMA3-8B's published widths (``configs/llama3_8b.py``:
    d_model 4096, 32 query and 8 KV heads of dim 128, d_ff 14336, vocab
    128256) with seeded random weights, W4A4 at the paper's defaults
    (``QuantSpec``: rank 128, group 128), depth cut to ``N_LAYERS``.
    ``quantize_params`` and ``fuse_params`` run on the host CPU, as offline
    quantization: the TPU compiler takes minutes to compile one SVD at these
    widths (eigh dominates). The packed tree then moves to the chip.
(c) Kernels against the oracle. Each routed kind (``dual`` and
    ``dual_fused`` at decode M and prefill M, ``paged_decode``, ``ragged``)
    runs on layer 0's packs or on seeded attention inputs and is compared
    with ``kernels/ref.py`` under ``jax.default_matmul_precision("highest")``,
    within the tolerances stated below.
(d) Bucketed paged engine and (e) ragged engine serve the same requests:
    a shared prefix and a few hundred prompt tokens each. Every request must
    end DONE, no route may be an oracle (``/ref``) route, every kernel kind
    must appear, every downloaded logits row must be finite, and the ragged
    engine must compile one executable.

Lines before the last are bring-up observations (wall and compile seconds,
peak device memory, routing counters, tokens served), not benchmark numbers.
The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_LAYERS = 4  # of LLaMA3-8B's 32: host quantization costs ~1 min per layer
SEED = 0
SLOTS = 8  # engine batch slots = the decode M of every linear
PAGE = 16
TOKEN_BUDGET = 256  # ragged rows per step = the prefill M of phase (c)
N_REQUESTS = 12
SHARED_PREFIX = 256  # prompt tokens every request shares
TAIL = (33, 64)  # per-request prompt tail, inclusive range of lengths
MAX_NEW = 32
MAX_LEN = SHARED_PREFIX + TAIL[1] + MAX_NEW

# Tolerances, kernel against oracle, both run on the chip.
# Linears: both sides compute the same int8 products with exact int32
# accumulation. They may differ only in the f32 rounding of scale products,
# sums and divisions, which can also move an activation, or an element of the
# requantized low-rank intermediate H, across a rounding tie by one 4-bit
# level. One X flip moves an output by about one product term of a 4096- to
# 14336-long dot; one H flip by a seventh of an H group's range times one V
# entry: each up to about a tenth of the output RMS, so a few may meet in one
# output. The bf16 outputs themselves round to 2**-8 relative, so a one-ULP
# difference on every output gives a mean near 0.003 of the RMS; a wrong
# scale, group or segment gives a mean of order one.
LINEAR_MAX_ERR = 0.25  # max |kernel - oracle| / rms(oracle)
LINEAR_MEAN_ERR = 0.005  # mean |kernel - oracle| / rms(oracle)
# Attention: the kernels accumulate the online softmax in f32 while the
# oracles round probabilities to bf16 before the value dot (the bucketed
# decode path's numerics), so agreement is to bf16 tolerance: the bound the
# interpret-mode tests use.
ATTN_ATOL, ATTN_RTOL = 0.03, 0.05

REQUIRED_ROUTES = {
    "paged": ("dual_fused/decode", "dual_fused/prefill", "dual/decode",
              "dual/prefill", "paged_decode/kernel"),
    "ragged": ("dual_fused/prefill", "dual/prefill", "ragged/kernel"),
}


class SmokeFailure(RuntimeError):
    """A bring-up check that did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (XLA and Mosaic). Trace events
    are left out: a jitted kernel wrapper traced inside an outer trace
    reports a span nested in its caller's, so their sum overcounts."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def timed(name: str, clock: CompileClock, fn, *args):
    t0, c0 = time.monotonic(), clock.seconds
    out = fn(*args)
    print(f"[{name}] wall {time.monotonic() - t0:.1f} s, backend compile "
          f"{clock.seconds - c0:.1f} s", flush=True)
    return out


# ---------------------------------------------------------------------------
# (a) device check
# ---------------------------------------------------------------------------


def device_check():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX reports platform {dev.platform!r}"
        )
    print(f"[a] device {dev.device_kind}, {len(devices)} visible", flush=True)
    return dev, len(devices)


# ---------------------------------------------------------------------------
# (b) quantize
# ---------------------------------------------------------------------------


def quantize(cfg, dev):
    import jax

    from repro.configs import QuantSpec
    from repro.core.twinquant import fuse_params, quantize_params
    from repro.models.registry import get_model

    spec = QuantSpec(mode="w4a4")
    print(f"[b] {cfg.name}: depth cut {cfg.n_layers}/32 layers, widths as "
          f"published; W4A4 rank {spec.rank} group {spec.group_size}, "
          "quantized on the host CPU", flush=True)
    with jax.default_device(jax.devices("cpu")[0]):
        params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(SEED))
        qparams = jax.block_until_ready(
            fuse_params(quantize_params(params, cfg, spec))
        )
    return jax.device_put(qparams, dev)


# ---------------------------------------------------------------------------
# (c) kernels against the oracle
# ---------------------------------------------------------------------------


def _routed(kind: str, path: str, fn, *args, **kw):
    """Run one dispatch entry; it must take exactly the ``kind/path`` route."""
    from repro.kernels.dispatch import dispatch_counters

    before = dispatch_counters()
    out = fn(*args, **kw)
    after = dispatch_counters()
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    check(delta == {f"{kind}/{path}": 1}, f"{kind}: expected route {path}, got {delta}")
    return out


def _linear_errors(y, ref):
    import numpy as np

    y, ref = np.asarray(y, np.float32), np.asarray(ref, np.float32)
    check(bool(np.isfinite(y).all()), "non-finite kernel output")
    rms = float(np.sqrt(np.mean(ref * ref)))
    d = np.abs(y - ref)
    return float(d.max()) / rms, float(d.mean()) / rms, float(np.mean(d == 0))


def _attn_check(name: str, y, ref) -> None:
    import numpy as np

    y, ref = np.asarray(y, np.float32), np.asarray(ref, np.float32)
    err = float(np.abs(y - ref).max())
    print(f"[c] {name}: max |kernel - oracle| {err:.4f}", flush=True)
    check(bool(np.isfinite(y).all()), f"{name}: non-finite kernel output")
    check(bool(np.allclose(y, ref, atol=ATTN_ATOL, rtol=ATTN_RTOL)),
          f"{name}: kernel disagrees with the oracle (max err {err:.4f})")


def check_linears(layer0: dict) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.dispatch import fused_linear, quant_linear
    from repro.kernels.ref import dual_gemm_group_ref, dual_gemm_ref
    from repro.models.common import group_weights_of, weights_of

    packs = [
        ("dual_fused", "qkv", group_weights_of(layer0["attn"]["qkv"])),
        ("dual_fused", "gate_up", group_weights_of(layer0["mlp"]["gate_up"])),
        ("dual", "o", weights_of(layer0["attn"]["o"])),
        ("dual", "down", weights_of(layer0["mlp"]["down"])),
    ]
    key = jax.random.PRNGKey(SEED + 1)
    for m, path in ((SLOTS, "decode"), (TOKEN_BUDGET, "prefill")):
        for kind, name, w in packs:
            key, kx = jax.random.split(key)
            x = jax.random.normal(kx, (m, w.kdim), jnp.float32).astype(jnp.bfloat16)
            if kind == "dual_fused":
                y = jnp.concatenate(_routed(kind, path, fused_linear, x, w), axis=-1)
                ref_fn = dual_gemm_group_ref
            else:
                y = _routed(kind, path, quant_linear, x, w)
                ref_fn = dual_gemm_ref
            with jax.default_matmul_precision("highest"):
                ref = ref_fn(x, w)
            mx, mean, exact = _linear_errors(y, ref)
            print(f"[c] {kind}/{path} {name} M={m}: max err {mx:.2e}, mean err "
                  f"{mean:.2e} (of output RMS), {exact:.4f} bit-exact", flush=True)
            check(mx <= LINEAR_MAX_ERR and mean <= LINEAR_MEAN_ERR,
                  f"{kind}/{path} {name}: kernel disagrees with the oracle")


def check_attention(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.dispatch import paged_decode, ragged_attention
    from repro.kernels.paged_attention import paged_decode_ref
    from repro.kernels.ragged_attention import ragged_attention_ref

    rng = np.random.default_rng(SEED + 2)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, maxp = SLOTS, -(-MAX_LEN // PAGE)
    n_pages = b * maxp

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    kp, vp = normal(n_pages, PAGE, kv, hd), normal(n_pages, PAGE, kv, hd)
    perm = rng.permutation(n_pages)

    def tables(rows_per_slot):
        bt = np.full((b, maxp), -1, np.int32)
        for s, n in enumerate(rows_per_slot):
            n_pg = -(-int(n) // PAGE)
            bt[s, :n_pg] = perm[s * maxp: s * maxp + n_pg]
        return jnp.asarray(bt)

    # paged decode: one new row per slot over a committed prefix of pos rows
    pos = rng.integers(1, MAX_LEN - 1, size=b).astype(np.int32)
    args = (normal(b, 1, h, hd), kp, vp, normal(b, 1, kv, hd),
            normal(b, 1, kv, hd), tables(pos + 1), jnp.asarray(pos))
    out = _routed("paged_decode", "kernel", paged_decode, *args, commit=False)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_ref(*args, commit=False)
    _attn_check(f"paged_decode B={b} pages of {PAGE}", out, ref)

    # ragged: four decode rows, a chunk continuing a cached prompt, a cold
    # chunk, two idle slots and pad rows (slot == B) up to the token budget
    t = TOKEN_BUDGET
    n_warm = (t - 4) * 2 // 5  # rows of the chunk that continues a prompt
    n_cold = n_warm * 6 // 5  # rows of the cold chunk
    ctx = np.zeros(b, np.int32)
    ctx[:4] = rng.integers(1, MAX_LEN - 1, size=4)
    ctx[4] = min(4 * PAGE, MAX_LEN - n_warm - 1)
    slot = np.full(t, b, np.int32)
    rpos = np.zeros(t, np.int32)
    slot[:4], rpos[:4] = np.arange(4), ctx[:4]
    cold = 4 + n_warm
    slot[4:cold], rpos[4:cold] = 4, ctx[4] + np.arange(n_warm)
    slot[cold:cold + n_cold], rpos[cold:cold + n_cold] = 5, np.arange(n_cold)
    rows = ctx.copy()
    for s in range(b):
        if (slot == s).any():
            rows[s] = rpos[slot == s].max() + 1
    args = (normal(t, h, hd), kp, vp, normal(t, kv, hd), normal(t, kv, hd),
            tables(rows), jnp.asarray(slot), jnp.asarray(rpos), jnp.asarray(ctx))
    out = _routed("ragged", "kernel", ragged_attention, *args)
    with jax.default_matmul_precision("highest"):
        ref = ragged_attention_ref(*args)
    real = slot < b
    _attn_check(f"ragged T={t}", np.asarray(out)[real], np.asarray(ref)[real])


def check_kernels(qparams, cfg) -> None:
    import jax

    check_linears(jax.tree.map(lambda a: a[0], qparams["layers"]))
    check_attention(cfg)


# ---------------------------------------------------------------------------
# (d), (e) serving
# ---------------------------------------------------------------------------


def make_prompts(cfg):
    import numpy as np

    rng = np.random.default_rng(SEED + 3)
    prefix = rng.integers(0, cfg.vocab, SHARED_PREFIX)
    return [
        np.concatenate([prefix, rng.integers(0, cfg.vocab, n)]).astype(np.int32)
        for n in rng.integers(TAIL[0], TAIL[1] + 1, N_REQUESTS)
    ]


def serve(mode: str, cfg, qparams, prompts) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import ContinuousBatchingEngine, Request, RequestState
    from repro.models import common as C

    kw = dict(ragged=True, token_budget=TOKEN_BUDGET) if mode == "ragged" else {}
    engine = ContinuousBatchingEngine(cfg, qparams, batch_slots=SLOTS,
                                      max_len=MAX_LEN, paged=True,
                                      page_size=PAGE, **kw)
    requests = [Request(jnp.asarray(p), max_new=MAX_NEW) for p in prompts]
    logits = {"rows": 0, "nonfinite": 0}

    def tap(last, where):
        rows = last.reshape(-1, last.shape[-1])[:, : cfg.vocab]
        logits["rows"] += rows.shape[0]
        logits["nonfinite"] += int((~np.isfinite(rows).all(axis=-1)).sum())
        return last

    prev = C.set_logits_tap(tap)
    try:
        engine.serve(requests)
    finally:
        C.set_logits_tap(prev)

    failed = [r for r in requests if r.status != RequestState.DONE]
    for r in failed:
        print(f"[{mode}] request {r.request_id}: {r.status} {r.error}: "
              f"{r.error_detail}", flush=True)
    routes = engine.routing()
    cs = engine.compile_stats()
    tokens = sum(len(r.out) for r in requests)
    print(f"[{mode}] {len(requests)} requests, {tokens} tokens served; "
          f"{logits['rows']} logits rows checked; routes {dict(sorted(routes.items()))}; "
          f"compile stats {cs}", flush=True)
    check(not failed and engine.stats["requests_failed"] == 0,
          f"{mode}: {len(failed)} request(s) did not finish DONE")
    check(not [k for k in routes if "/ref" in k], f"{mode}: oracle routes taken: {routes}")
    missing = [k for k in REQUIRED_ROUTES[mode] if not routes.get(k)]
    check(not missing, f"{mode}: kernel routes missing: {missing}")
    check(logits["rows"] > 0 and logits["nonfinite"] == 0,
          f"{mode}: {logits['nonfinite']} non-finite logits rows")
    check(tokens == N_REQUESTS * MAX_NEW, f"{mode}: {tokens} tokens served")
    if mode == "ragged":
        check(cs["ragged_traces"] == 1 and cs["prefill_traces"] == 0,
              f"ragged: expected one executable, got {cs}")
    engine.check_page_invariants()


def main() -> int:
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: {SRC / 'repro'} not found; run from a checkout")
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        # phase (b) quantizes on the host, so keep JAX's CPU backend loaded
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path.insert(0, str(SRC))
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev, count = device_check()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    print(f"[a] compile cache {cache}", flush=True)

    cfg = get_config("llama3-8b").replace(n_layers=N_LAYERS)
    qparams = timed("b quantize", clock, quantize, cfg, dev)
    timed("c kernels", clock, check_kernels, qparams, cfg)
    prompts = make_prompts(cfg)
    print(f"[d] {N_REQUESTS} prompts of {min(map(len, prompts))}-"
          f"{max(map(len, prompts))} tokens sharing {SHARED_PREFIX}, "
          f"max_new {MAX_NEW}", flush=True)
    timed("d paged engine", clock, serve, "paged", cfg, qparams, prompts)
    timed("e ragged engine", clock, serve, "ragged", cfg, qparams, prompts)
    stats = dev.memory_stats() or {}
    print(f"peak device memory {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
          "(bring-up observation)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
