"""Trace-time contract-layer tests: divisibility/grid-coverage violations and
over-budget VMEM launches must raise a readable ContractError (never a bare
assert tuple, a Mosaic error, or a silent ref fallback), malformed packs must
be diagnosed at the dispatch entries, ref fallbacks must record their reason
in the dispatch counters, and a corrupt tune cache must degrade with a
warning instead of crashing or poisoning routing."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.autotune import (
    TuneCache,
    cache_key,
    candidate_blocks,
    get_blocks,
    heuristic_blocks,
)
from repro.kernels.contracts import (
    ContractError,
    check_blocks,
    check_vmem,
    validate_dual_gemm,
    validate_dual_gemm_group,
    validate_dual_gemv,
    validate_dual_gemv_group,
    validate_w4a16,
    vmem_footprint,
)
from repro.kernels.dispatch import (
    dispatch_counters,
    fused_linear,
    quant_linear,
    reset_dispatch_counters,
    w4a16_linear,
)
from repro.kernels.ref import (
    fuse_twinquant_weights,
    pack_rows_groupsplit,
    pack_twinquant_weights,
    quantize_rows_ref,
)
from repro.kernels.twinquant_dual_gemm import dual_gemm
from repro.kernels.twinquant_dual_gemv import dual_gemv
from repro.kernels.w4a16_gemm import w4a16_gemm


def _make_pack(key, K, N, r, a_bits=4, group=128):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    U = jax.random.normal(k1, (K, r)) * 0.1
    V = jax.random.normal(k2, (r, N)) * 0.1
    R = jax.random.normal(k3, (K, N)) * 0.05
    return pack_twinquant_weights(U, V, R, a_bits=a_bits, group=group), k4


# ---------------------------------------------------------------------------
# divisibility / grid-coverage contracts
# ---------------------------------------------------------------------------


def test_validate_dual_gemm_accepts_canonical_shapes():
    validate_dual_gemm(256, 512, 1024, 64, 128, 32, 128, 256, 512)


@pytest.mark.parametrize("field,args,fragment", [
    # m=200 not a multiple of block_m=128
    ("M", (200, 512, 1024, 64, 128, 32, 128, 256, 512), "M % block_m"),
    # n=500 not a multiple of block_n=256
    ("N", (256, 500, 1024, 64, 128, 32, 128, 256, 512), "N % block_n"),
    # block_k=384 not a multiple of group=256
    ("bk", (256, 512, 1536, 64, 256, 32, 128, 256, 384), "block_k % group"),
    # rank=60 not a multiple of rgroup=32
    ("r", (256, 512, 1024, 60, 128, 32, 128, 256, 512), "rank % rgroup"),
])
def test_validate_dual_gemm_violations_are_readable(field, args, fragment):
    with pytest.raises(ContractError) as ei:
        validate_dual_gemm(*args)
    assert fragment in str(ei.value)
    assert "hint" in str(ei.value)


def test_validate_dual_gemv_decode_bound():
    with pytest.raises(ContractError, match="DECODE_M_MAX"):
        validate_dual_gemv(9, 512, 1024, 64, 128, 32, 256, decode_m_max=8)


def test_validate_group_segment_straddle():
    # block_n=256 does not tile the 128-wide second segment
    with pytest.raises(ContractError, match="segment 1"):
        validate_dual_gemv_group(
            4, 1024, 128, (512, 128), (64, 32), (32, 32), 256, decode_m_max=8
        )
    with pytest.raises(ContractError, match="segment 1"):
        validate_dual_gemm_group(
            256, 1024, 128, (512, 128), (64, 32), (32, 32), 128, 256, 512
        )


def test_validate_w4a16_violation():
    with pytest.raises(ContractError, match="K % block_k"):
        validate_w4a16(128, 256, 700, 128, 128, 256, 512)


# ---------------------------------------------------------------------------
# TPU layout rules: enforced at trace time on every backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block,array,legal", [
    ((4, 512), (32, 14336), False),    # block_k=512 over group 128: 4 scale rows
    ((8, 512), (32, 14336), True),     # block_k=1024: 8 scale rows
    ((4, 512), (4, 14336), True),      # the whole K/G extent
    ((128, 64), (256, 512), False),    # block_n below a lane tile
    ((4, 4096), (8, 4096), False),     # block_m below a sublane tile
    ((8, 1, 1024), (8, 1, 1024), True),  # leading dims are untiled
])
def test_block_rule(block, array, legal):
    specs = [("blk", block, array)]
    if legal:
        check_blocks("k", specs)
    else:
        with pytest.raises(ContractError, match=r"\(8, 128\) block rule"):
            check_blocks("k", specs)


def test_lane_rule_refuses_sub_lane_group():
    """The dual kernels slice each scale group at a dynamic lane offset, so
    a 64-wide group is refused by the validators and never routed to them;
    the weight-only kernel slices groups statically and keeps it."""
    with pytest.raises(ContractError, match="group % 128"):
        validate_dual_gemv(8, 512, 1024, 64, 64, 32, 256, decode_m_max=8)
    with pytest.raises(ContractError, match="group % 128"):
        validate_dual_gemm(256, 512, 1024, 64, 64, 32, 128, 256, 512)
    assert heuristic_blocks("dual_decode", 1, 128, 256, 64) is None
    assert heuristic_blocks("dual_prefill", 256, 512, 1024, 64) is None
    assert heuristic_blocks("w4a16", 256, 512, 1024, 64) is not None


def test_kernel_wrapper_refuses_illegal_block_in_interpret_mode():
    w, key = _make_pack(jax.random.PRNGKey(10), 512, 256, 32)
    x = jax.random.normal(key, (128, 512)).astype(jnp.bfloat16)
    with pytest.raises(ContractError, match="block rule"):
        dual_gemm(x, w, block_m=128, block_n=64, block_k=512, interpret=True)
    wq, ws = quantize_rows_ref(jax.random.normal(key, (512, 256)) * 0.1, 128, 4)
    wp = pack_rows_groupsplit(wq, 128)
    with pytest.raises(ContractError, match="block rule"):
        w4a16_gemm(x[:8], wp, ws, group=128, block_m=4, block_n=128,
                   block_k=128, interpret=True)


@pytest.mark.parametrize("kind,m,n,k", [
    ("dual_prefill", 256, 4096, 4096),
    ("dual_prefill", 256, 4096, 14336),
    ("dual_prefill", 64, 384, 768),
    ("dual_prefill", 32, 256, 1536),
    ("w4a16", 256, 14336, 4096),
    ("w4a16", 16, 384, 640),
])
def test_candidate_blocks_pass_the_contract(kind, m, n, k):
    """Every block triple the autotuner can hand the dispatch layer is one
    the contract (and so the chip's compiler) accepts."""
    group = 128
    cands = candidate_blocks(kind, m, n, k, group, 128)
    assert cands and heuristic_blocks(kind, m, n, k, group, 128) in cands
    for bm, bn, bk in cands:
        mp = -(-m // bm) * bm  # dispatch pads M to the block
        if kind == "w4a16":
            validate_w4a16(mp, n, k, group, bm, bn, bk, budget=2**40)
        else:
            validate_dual_gemm(mp, n, k, 128, group, 128, bm, bn, bk, budget=2**40)


def test_stale_cache_entry_breaking_block_rule_degrades(tmp_path):
    cache = TuneCache(tmp_path)
    key = cache_key("w4a16", 256, 512, 1024, 64)
    cache.store(key, (128, 512, 64))  # a 64-wide x block: not a lane tile
    assert get_blocks("w4a16", 256, 512, 1024, 64, cache=TuneCache(tmp_path)) == \
        heuristic_blocks("w4a16", 256, 512, 1024, 64)


def test_kernel_wrapper_raises_contract_error_not_assert(monkeypatch):
    """Deliberately violating a BlockSpec divisibility contract at a kernel
    wrapper produces the readable ContractError (acceptance criterion)."""
    w, key = _make_pack(jax.random.PRNGKey(0), 512, 256, 32)
    x = jax.random.normal(key, (200, 512)).astype(jnp.bfloat16)  # 200 % 128 != 0
    with pytest.raises(ContractError, match="M % block_m"):
        dual_gemm(x, w, block_m=128, block_n=256, block_k=512, interpret=True)
    xb = jax.random.normal(key, (16, 512)).astype(jnp.bfloat16)  # M > decode bound
    with pytest.raises(ContractError, match="DECODE_M_MAX"):
        dual_gemv(xb, w, block_n=256, interpret=True)


# ---------------------------------------------------------------------------
# VMEM footprint estimator
# ---------------------------------------------------------------------------


def test_vmem_footprint_double_buffers_streamed():
    total, breakdown = vmem_footprint([
        ("x", (128, 512), jnp.bfloat16, "streamed"),
        ("u", (256, 64), jnp.int8, "pinned"),
        ("acc", (128, 256), jnp.float32, "scratch"),
    ])
    assert breakdown["x"] == 128 * 512 * 2 * 2  # bf16, double-buffered
    assert breakdown["u"] == 256 * 64           # pinned once
    assert breakdown["acc"] == 128 * 256 * 4
    assert total == sum(breakdown.values())


def test_check_vmem_over_budget_is_readable():
    with pytest.raises(ContractError) as ei:
        check_vmem(
            "dual_gemm",
            [("x", (4096, 4096), jnp.float32, "streamed")],
            budget=16 * 2**20,
        )
    msg = str(ei.value)
    assert "VMEM footprint" in msg and "x" in msg and "MiB" in msg


def test_wrapper_vmem_budget_env(monkeypatch):
    """An otherwise-valid launch is rejected when the budget is tightened —
    a readable contract error, not a Mosaic allocation failure."""
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(64 * 1024))
    w, key = _make_pack(jax.random.PRNGKey(1), 512, 256, 32)
    x = jax.random.normal(key, (128, 512)).astype(jnp.bfloat16)
    with pytest.raises(ContractError, match="VMEM footprint"):
        dual_gemm(x, w, block_m=128, block_n=256, block_k=512, interpret=True)


# ---------------------------------------------------------------------------
# pack contracts at the dispatch entries
# ---------------------------------------------------------------------------


def test_malformed_pack_diagnosed_not_silently_ref():
    """A pack whose fields disagree (here: scales for the wrong K) raises a
    ContractError diagnostic instead of silently routing to ref."""
    w, key = _make_pack(jax.random.PRNGKey(2), 512, 256, 32)
    bad = dataclasses.replace(w, us=w.us[:2])  # covers K=256, activation K=512
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    reset_dispatch_counters()
    with pytest.raises(ContractError, match="us"):
        quant_linear(x, bad)
    assert dispatch_counters() == {}  # rejected before any route was recorded


def test_malformed_pack_wrong_dtype():
    w, key = _make_pack(jax.random.PRNGKey(3), 512, 256, 32)
    bad = dataclasses.replace(w, up=w.up.astype(jnp.float32))
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    with pytest.raises(ContractError, match="int8"):
        quant_linear(x, bad)


def test_malformed_group_pack_diagnosed():
    key = jax.random.PRNGKey(4)
    w1, key = _make_pack(key, 512, 256, 32)
    w2, key = _make_pack(key, 512, 128, 32)
    gw = fuse_twinquant_weights([w1, w2])
    bad = dataclasses.replace(gw, rp=gw.rp[:, :256])  # width != sum(seg_n)
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    with pytest.raises(ContractError, match="segment widths"):
        fused_linear(x, bad)


def test_malformed_w4a16_pack_diagnosed():
    key = jax.random.PRNGKey(5)
    wq, ws = quantize_rows_ref(jax.random.normal(key, (512, 256)) * 0.1, 128, 4)
    wp = pack_rows_groupsplit(wq, 128)
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    with pytest.raises(ContractError, match="scale rows"):
        w4a16_linear(x, wp, ws[:2], group=128)


def test_odd_but_consistent_pack_still_routes_ref():
    """Pack contracts check INTERNAL consistency only: an odd-but-coherent
    shape (N=100) remains a routing decision, exactly as before."""
    w, key = _make_pack(jax.random.PRNGKey(6), 512, 100, 32)
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    reset_dispatch_counters()
    y = quant_linear(x, w)  # must not raise
    assert y.shape == (4, 100)
    assert dispatch_counters().get("dual/ref") == 1


# ---------------------------------------------------------------------------
# ref fallback reasons in the dispatch counters
# ---------------------------------------------------------------------------


def test_ref_fallback_reason_counters():
    key = jax.random.PRNGKey(7)
    reset_dispatch_counters()

    w_odd_n, key = _make_pack(key, 512, 100, 32)      # untileable N
    x_dec = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    quant_linear(x_dec, w_odd_n)                      # decode-regime M

    x_pre = jax.random.normal(key, (64, 512)).astype(jnp.bfloat16)
    quant_linear(x_pre, w_odd_n)                      # prefill-regime M

    w_ok, key = _make_pack(key, 512, 256, 32)
    quant_linear(x_dec, w_ok, impl="ref")             # intentional oracle

    c = dispatch_counters()
    assert c["dual/ref"] == 3
    # ...but the reasons are now distinguishable:
    assert c["dual/ref[decode_untileable]"] == 1
    assert c["dual/ref[prefill_untileable]"] == 1
    assert c["dual/ref[forced]"] == 1
    # kernel routes record no reason suffix
    quant_linear(x_dec, w_ok)
    assert dispatch_counters().get("dual/decode") == 1
    assert not any(k.startswith("dual/decode[") for k in dispatch_counters())


def test_ref_reason_keys_never_look_like_decode_launches():
    """compare.py's decode_launches sums keys ending '/decode' — reason keys
    must never match that suffix."""
    reset_dispatch_counters()
    w, key = _make_pack(jax.random.PRNGKey(8), 512, 100, 32)
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    quant_linear(x, w)
    assert not any(k.endswith("/decode") for k in dispatch_counters())


# ---------------------------------------------------------------------------
# TuneCache robustness: corrupt artifacts degrade with a warning
# ---------------------------------------------------------------------------


def _expect_heuristic_with_warning(tmp_path, match):
    with pytest.warns(UserWarning, match=match):
        got = get_blocks("dual_prefill", 256, 512, 1024, 128, 64,
                         cache=TuneCache(tmp_path))
    assert got == heuristic_blocks("dual_prefill", 256, 512, 1024, 128, 64)


def test_corrupt_json_cache_warns_and_degrades(tmp_path):
    (tmp_path / "dual_prefill.json").write_text("{not json at all")
    _expect_heuristic_with_warning(tmp_path, "unreadable tune cache")


def test_wrong_schema_cache_warns_and_degrades(tmp_path):
    (tmp_path / "dual_prefill.json").write_text(
        json.dumps({"schema": 99, "entries": {"dual_prefill/x": {"blocks": [1, 2, 3]}}})
    )
    _expect_heuristic_with_warning(tmp_path, "schema")


def test_non_object_cache_warns_and_degrades(tmp_path):
    (tmp_path / "dual_prefill.json").write_text('["schema", 1]')
    _expect_heuristic_with_warning(tmp_path, "JSON object")


def test_garbage_blocks_entry_warns_and_degrades(tmp_path):
    key = cache_key("dual_prefill", 256, 512, 1024, 128, 64)
    (tmp_path / "dual_prefill.json").write_text(json.dumps({
        "schema": 1,
        "entries": {
            key: {"blocks": ["big", None, {}]},
            "dual_prefill/other": "not even a dict",
        },
    }))
    _expect_heuristic_with_warning(tmp_path, "malformed tune-cache entry")


def test_corrupt_cache_does_not_poison_routing(tmp_path, monkeypatch):
    """End-to-end: with a corrupt cache dir active, dispatch still routes and
    computes correctly (heuristic blocks, no crash)."""
    import warnings as _warnings

    import repro.kernels.autotune as autotune_mod

    (tmp_path / "dual_decode.json").write_text("}{")
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    monkeypatch.setattr(autotune_mod, "_default_cache", None)
    w, key = _make_pack(jax.random.PRNGKey(9), 512, 256, 32)
    x = jax.random.normal(key, (4, 512)).astype(jnp.bfloat16)
    reset_dispatch_counters()
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore", UserWarning)
        y = quant_linear(x, w)
    assert y.shape == (4, 256)
    assert dispatch_counters().get("dual/decode") == 1
    monkeypatch.setattr(autotune_mod, "_default_cache", None)
