"""Dispatch-layer tests: shape-regime routing, kernel/oracle agreement on all
three paths (prefill kernel / decode kernel / jnp ref), autotuner cache
round-trips, and dispatch-counter accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.autotune import (
    TuneCache,
    autotune_blocks,
    cache_key,
    candidate_blocks,
    get_blocks,
    heuristic_blocks,
)
from repro.kernels.dispatch import (
    DECODE_M_MAX,
    QuantLinear,
    classify_dual,
    classify_w4a16,
    dispatch_counters,
    quant_linear,
    reset_dispatch_counters,
    w4a16_linear,
)
from repro.kernels.ops import pick_blocks
from repro.kernels.ref import (
    dual_gemm_ref,
    pack_rows_groupsplit,
    pack_twinquant_weights,
    quantize_rows_ref,
    w4a16_gemm_ref,
)


def _make_pack(key, K, N, r, a_bits=4, group=128):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    U = jax.random.normal(k1, (K, r)) * 0.1
    V = jax.random.normal(k2, (r, N)) * 0.1
    R = jax.random.normal(k3, (K, N)) * 0.05
    return pack_twinquant_weights(U, V, R, a_bits=a_bits, group=group), k4


def _assert_bf16_close(y_k, y_ref, max_ulp=2):
    """<=2 bf16 ULP: identical math modulo f32 reassociation (test_kernels)."""
    a = np.asarray(jnp.asarray(y_k, jnp.bfloat16)).view(np.uint16).astype(np.int32)
    b = np.asarray(jnp.asarray(y_ref, jnp.bfloat16)).view(np.uint16).astype(np.int32)
    ka = np.where(a & 0x8000, 0x7FFF - (a & 0x7FFF), 0x8000 + a)
    kb = np.where(b & 0x8000, 0x7FFF - (b & 0x7FFF), 0x8000 + b)
    ulp = np.abs(ka - kb)
    assert ulp.max() <= max_ulp, f"{(ulp > max_ulp).sum()} elements differ (max {ulp.max()})"


# ---------------------------------------------------------------------------
# routing classification
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,k,expected", [
    (1, 256, 512, "decode"),
    (3, 384, 512, "decode"),
    (8, 256, 512, "decode"),
    (DECODE_M_MAX, 128, 256, "decode"),
    (DECODE_M_MAX + 1, 256, 512, "prefill"),
    (64, 256, 512, "prefill"),
    (1024, 384, 1024, "prefill"),
    (3, 100, 512, "ref"),      # N not 128-aligned
    (64, 100, 512, "ref"),
    (4, 256, 300, "ref"),      # K not a group multiple
    (64, 384, 192, "ref"),     # old pick_blocks bk bug: 192 % 128 != 0
])
def test_classify_dual_regimes(m, n, k, expected):
    route = classify_dual(m, n, k, group=128, rgroup=32, rank=32)
    assert route.path == expected, route
    if expected == "ref":
        assert route.blocks is None
    else:
        bm, bn, bk = route.blocks
        assert n % bn == 0
        if expected == "prefill":
            assert k % bk == 0 and bk % 128 == 0


def test_classify_w4a16_regimes():
    assert classify_w4a16(16, 256, 512, 128).path == "prefill"
    assert classify_w4a16(16, 100, 512, 128).path == "ref"
    assert classify_w4a16(16, 256, 300, 128).path == "ref"


def test_pick_blocks_untileable_returns_none():
    """The two old fallback bugs must now surface as None (-> ref route)."""
    assert pick_blocks(64, 100, 512, 128) is None  # was bn = n = 100
    assert pick_blocks(64, 384, 300, 128) is None  # was bk = max(300, 128)
    assert pick_blocks(64, 384, 192, 128) is None  # 192 % 128 != 0
    blocks = pick_blocks(64, 384, 512, 128)
    assert blocks is not None and 384 % blocks[1] == 0 and 512 % blocks[2] == 0


# ---------------------------------------------------------------------------
# kernel/oracle agreement through the dispatcher (all three paths)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 3, 8])
@pytest.mark.parametrize("a_bits", [4, 8])
def test_decode_path_matches_oracle(m, a_bits):
    w, kx = _make_pack(jax.random.PRNGKey(m * 10 + a_bits), 512, 256, 64, a_bits)
    x = (jax.random.normal(kx, (m, 512)) * 2).astype(jnp.bfloat16)
    assert classify_dual(m, 256, 512, 128, w.rgroup, w.rank).path == "decode"
    y = quant_linear(x, w, impl="kernel", interpret=True)
    y_ref = dual_gemm_ref(x, w)
    # the decode schedule reproduces the oracle's accumulation order exactly
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    )


def test_prefill_path_matches_oracle():
    w, kx = _make_pack(jax.random.PRNGKey(5), 512, 256, 64)
    x = (jax.random.normal(kx, (48, 512)) * 2).astype(jnp.bfloat16)  # pads to bm
    assert classify_dual(48, 256, 512, 128, w.rgroup, w.rank).path == "prefill"
    y = quant_linear(x, w, impl="kernel", interpret=True)
    _assert_bf16_close(y, dual_gemm_ref(x, w))


@pytest.mark.parametrize("m,n,k", [
    (1, 100, 512),   # odd N -> ref
    (3, 96, 256),    # N < 128 -> ref
    (8, 100, 512),
    (33, 100, 512),  # odd N in the prefill regime -> ref
])
def test_ref_path_odd_shapes_no_assert(m, n, k):
    """Untileable shapes must route to the oracle, not trip kernel asserts."""
    w, kx = _make_pack(jax.random.PRNGKey(m + n), k, n, 32)
    x = (jax.random.normal(kx, (m, k)) * 2).astype(jnp.bfloat16)
    assert classify_dual(m, n, k, 128, w.rgroup, w.rank).path == "ref"
    y = quant_linear(x, w, impl="kernel", interpret=True)  # impl hint ignored on ref
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(dual_gemm_ref(x, w), np.float32)
    )


def test_batch_dims_and_bias_through_dispatch():
    w, kx = _make_pack(jax.random.PRNGKey(9), 256, 128, 32)
    x = (jax.random.normal(kx, (2, 3, 256))).astype(jnp.bfloat16)  # M=6 -> decode
    b = jnp.arange(128, dtype=jnp.float32) * 0.01
    y = quant_linear(x, w, b, impl="kernel", interpret=True)
    assert y.shape == (2, 3, 128)
    y_ref = dual_gemm_ref(x.reshape(6, 256), w).reshape(2, 3, 128)
    y_ref = (y_ref.astype(jnp.float32) + b).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(y, np.float32), np.asarray(y_ref, np.float32))


def test_w4a16_ref_fallback_matches_oracle():
    key = jax.random.PRNGKey(2)
    k1, k2 = jax.random.split(key)
    wq, ws = quantize_rows_ref(jax.random.normal(k1, (256, 100)) * 0.1, 128, 4)
    wp = pack_rows_groupsplit(wq, 128)
    x = (jax.random.normal(k2, (5, 256))).astype(jnp.bfloat16)
    assert classify_w4a16(5, 100, 256, 128).path == "ref"
    y = w4a16_linear(x, wp, ws, group=128)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32),
        np.asarray(w4a16_gemm_ref(x, wp, ws, group=128), np.float32),
    )


def test_quantlinear_entrypoint():
    w, kx = _make_pack(jax.random.PRNGKey(11), 256, 128, 32)
    layer = QuantLinear(w)
    assert layer.route_for((4, 256)).path == "decode"
    assert layer.route_for((2, 64, 256)).path == "prefill"
    x = (jax.random.normal(kx, (4, 256)) * 2).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(layer(x), np.float32),
        np.asarray(dual_gemm_ref(x, w), np.float32),
    )


# ---------------------------------------------------------------------------
# dispatch counters
# ---------------------------------------------------------------------------


def test_dispatch_counters_record_paths():
    w, kx = _make_pack(jax.random.PRNGKey(21), 512, 256, 64)
    w_odd, _ = _make_pack(jax.random.PRNGKey(22), 512, 100, 32)
    x_dec = jnp.ones((4, 512), jnp.bfloat16)
    x_pre = jnp.ones((64, 512), jnp.bfloat16)
    reset_dispatch_counters()
    quant_linear(x_dec, w)
    quant_linear(x_dec, w)
    quant_linear(x_pre, w)
    quant_linear(x_dec, w_odd)
    c = dispatch_counters()
    assert c["dual/decode"] == 2
    assert c["dual/prefill"] == 1
    assert c["dual/ref"] == 1
    reset_dispatch_counters()
    assert dispatch_counters() == {}


# ---------------------------------------------------------------------------
# autotuner: heuristic determinism + persisted cache round-trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,k,group", [
    (4, 4096, 4096, 128), (256, 14336, 4096, 128), (64, 384, 768, 128),
    (8, 1024, 14336, 128), (1, 128, 256, 256),
])
def test_heuristic_blocks_valid_and_deterministic(m, n, k, group):
    kind = "dual_decode" if m <= DECODE_M_MAX else "dual_prefill"
    a = heuristic_blocks(kind, m, n, k, group)
    b = heuristic_blocks(kind, m, n, k, group)
    assert a == b and a is not None
    bm, bn, bk = a
    assert n % bn == 0 and bn % 128 == 0
    assert k % bk == 0 and bk % group == 0


def test_cache_key_uses_regime_not_exact_m():
    assert cache_key("dual", 1, 512, 256, 128, 32) == cache_key("dual", 8, 512, 256, 128, 32)
    assert cache_key("dual", 8, 512, 256, 128, 32) != cache_key("dual", 9, 512, 256, 128, 32)


def test_tune_cache_roundtrip(tmp_path):
    cache = TuneCache(tmp_path)
    key = cache_key("dual_prefill", 256, 512, 1024, 128, 64)
    cache.store(key, (64, 128, 256), best_us=12.5, candidates=9)
    # a fresh instance must read back the identical decision from disk
    fresh = TuneCache(tmp_path)
    assert fresh.lookup(key) == (64, 128, 256)
    # and the persisted winner takes precedence over the heuristic
    tuned = get_blocks("dual_prefill", 256, 512, 1024, 128, 64, cache=fresh)
    assert tuned == (64, 128, 256)
    assert tuned != heuristic_blocks("dual_prefill", 256, 512, 1024, 128, 64)
    # unknown shapes fall back to the deterministic heuristic
    assert get_blocks("dual_prefill", 256, 512, 2048, 128, 64, cache=fresh) == \
        heuristic_blocks("dual_prefill", 256, 512, 2048, 128, 64)


def test_stale_cache_entry_degrades_to_heuristic(tmp_path):
    """A cache entry that violates the tiling contract (stale/foreign/hand-
    edited) must fall back to the heuristic, never reach a kernel assert."""
    cache = TuneCache(tmp_path)
    key = cache_key("dual_prefill", 256, 512, 1024, 128, 64)
    cache.store(key, (128, 384, 768))  # 512 % 384 != 0, 1024 % 768 != 0
    fresh = TuneCache(tmp_path)
    assert fresh.lookup(key) == (128, 384, 768)  # raw lookup returns it
    assert get_blocks("dual_prefill", 256, 512, 1024, 128, 64, cache=fresh) == \
        heuristic_blocks("dual_prefill", 256, 512, 1024, 128, 64)


def test_tune_cache_file_is_schema1_json(tmp_path):
    import json

    cache = TuneCache(tmp_path)
    key = cache_key("dual_decode", 4, 256, 512, 128, 32)
    cache.store(key, (8, 256, 512))
    doc = json.loads((tmp_path / "dual_decode.json").read_text())
    assert doc["schema"] == 1
    assert doc["entries"][key]["blocks"] == [8, 256, 512]


def test_autotune_measured_sweep_persists(tmp_path):
    cache = TuneCache(tmp_path)
    calls = []

    def make_call(blocks):
        def run():
            calls.append(blocks)
            return jnp.zeros(())

        return run

    best = autotune_blocks("dual_prefill", make_call, 256, 512, 1024, 128, 64,
                           cache=cache, iters=1)
    cands = candidate_blocks("dual_prefill", 256, 512, 1024, 128, 64)
    assert best in cands
    assert set(calls) == set(cands)  # every candidate was measured
    assert TuneCache(tmp_path).lookup(
        cache_key("dual_prefill", 256, 512, 1024, 128, 64)
    ) == best


def test_autotune_untileable_returns_none(tmp_path):
    cache = TuneCache(tmp_path)
    assert autotune_blocks("dual_prefill", lambda b: lambda: jnp.zeros(()),
                           64, 100, 512, 128, cache=cache) is None
    assert not (tmp_path / "dual_prefill.json").exists()
