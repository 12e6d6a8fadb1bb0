"""Unified ragged engine step tests (docs/serving.md, DESIGN.md §16).

The correctness bar for ragged mode is the same A/B oracle paging is held
to, one level up: serving a request through the RAGGED engine — its prompt
chunked to the token budget and its rows sharing launches with other
requests' decode tokens — must be token-for-token identical to serving it
alone through the bucketed engine. On top of that: the ragged attention
kernel agrees with its jnp reference on a mixed decode/chunk/pad batch, the
dispatch layer records the ``ragged`` routing kind, a whole serving
lifetime compiles exactly ONE ragged executable (the compile-budget
sanitizer's ≤ 2 bound, vs O(log S_max) prefill buckets), decode throughput
never dips while a long prompt streams in, and the engine is loud (warn /
raise) rather than silently wrong when ragged mode cannot be used.

Chunk-boundary numerics: multi-chunk prompts carry one f32 reassociation
per chunk boundary vs the oracle's single fused dot (see
kernels/ragged_attention.py), so the interleave workloads here are pinned
to seeds/lengths verified token-identical for BOTH families.
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.sanitizers import (
    assert_compile_budget,
    guarded_decode,
    no_recompiles,
    page_invariant_checks,
)
from repro.configs import ModelConfig
from repro.kernels import dispatch
from repro.kernels.ragged_attention import (
    ragged_attention_kernel,
    ragged_attention_ref,
    ragged_work,
)
from repro.launch.serve import ContinuousBatchingEngine, Request
from repro.models import dense, olmoe

jax.config.update("jax_platform_name", "cpu")

DCFG = ModelConfig(
    name="tiny-ragged", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=256, remat=False,
)
# capacity_factor=4.0: ragged pad rows route through experts and consume
# capacity, so the tiny config needs headroom to stay drop-free
MCFG = ModelConfig(
    name="tiny-ragged-moe", family="moe", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, vocab=256, remat=False,
    n_experts=4, top_k=2, d_ff_expert=64, capacity_factor=4.0,
)


@pytest.fixture(scope="module")
def dparams():
    return dense.init_params(DCFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mparams():
    return olmoe.init_params(MCFG, jax.random.PRNGKey(1))


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=n).tolist() for n in lens]


def _solo(cfg, params, prompt, max_new=6):
    """Bucketed-engine solo serving: the token-equality oracle."""
    eng = ContinuousBatchingEngine(cfg, params, batch_slots=1, max_len=64)
    req = Request(jnp.asarray(prompt, jnp.int32), max_new=max_new)
    eng.serve([req])
    assert req.done
    return req.out


def _ragged_interleaved(cfg, params, prompts, token_budget=16, max_new=6):
    """Submit-then-step each prompt so chunked prefills overlap live decodes;
    the whole loop runs under the page-invariant sanitizer and the decode
    drain under the transfer guard."""
    eng = ContinuousBatchingEngine(
        cfg, params, batch_slots=3, max_len=64, paged=True,
        ragged=True, token_budget=token_budget,
    )
    reqs = [Request(jnp.asarray(p, jnp.int32), max_new=max_new) for p in prompts]
    with page_invariant_checks(eng):
        for r in reqs:
            eng.submit(r)
            eng.step()
        with guarded_decode():
            eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, reqs


# Ragged batches: name -> (B slots, T rows, ctx per slot, row runs). A run is
# (slot, first position, rows) placed at the next free row; slot None is pad
# rows. Trailing rows are pads too. Page size 8, 4 pages a slot.
_BATCHES = {
    # every row species: a decode row, a chunk continuing past its cache, the
    # first chunk of a cold prompt (ctx 0), pads
    "mixed": (3, 16, [13, 5, 0], [(0, 13, 1), (1, 5, 6), (2, 0, 7)]),
    # more slots than rows: 14 decode rows out of slot order, 6 slots idle
    "decode_only": (
        20, 16, [3 * b % 29 for b in range(20)],
        [(b, 3 * b % 29, 1) for b in (5, 0, 19, 7, 2, 11, 3, 17, 8, 1, 13, 4, 16, 9)],
    ),
    # slot 1's rows are not contiguous and span three query tiles, with
    # other slots' rows and pads between them
    "split_slot": (
        3, 48, [9, 20, 3],
        [(1, 20, 3), (0, 9, 1), (None, 0, 14), (2, 3, 5), (1, 23, 4)],
    ),
    # a slot with ctx 0 owning one row beside slots with cache behind them
    "ctx_zero": (4, 16, [0, 17, 30, 0], [(1, 17, 1), (0, 0, 1), (2, 30, 2)]),
    # one prompt chunk fills all T rows
    "full_chunk": (2, 16, [0, 11], [(1, 11, 16)]),
    # ... and at the engine's T = 256, where it spans two in-batch key tiles
    "full_chunk_256": (2, 256, [0, 24], [(1, 24, 256)]),
    # slots 1 and 2 hold committed pages but own no row this launch
    "empty_slots": (4, 16, [6, 25, 12, 9], [(0, 6, 1), (3, 9, 4)]),
}
# Variants whose slot rows span more than one in-batch key tile (T > 128):
# the kernel folds each key tile with its own online-softmax rescale, one
# f32 reassociation per tile boundary, so they match the parent schedule to
# one bf16 ulp rather than bitwise.
_KEY_TILE_SPLIT = {"full_chunk_256"}


def _mixed_batch(name="mixed", seed=3):
    """A ragged batch from ``_BATCHES``, with the pool pages each slot has
    mapped (committed pages plus the one being written, in random order) and
    the remaining pool pages unmapped. Returns the kernel's arguments, the
    real-row mask and the (B, maxp) mask of live (committed) pages."""
    B, T, ctx, runs = _BATCHES[name]
    rng = np.random.default_rng(seed)
    maxp, page, KV, H, hd = 4, 8, 2, 4, 16
    P = B * maxp

    def f(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.bfloat16)

    q, kt, vt = f(T, H, hd), f(T, KV, hd), f(T, KV, hd)
    kp, vp = f(P, page, KV, hd), f(P, page, KV, hd)
    ctx = np.asarray(ctx, np.int32)
    perm = rng.permutation(P)
    bt = np.full((B, maxp), -1, np.int32)
    for b in range(B):
        n_pg = min(-(-int(ctx[b]) // page) + 1, maxp)
        bt[b, :n_pg] = perm[b * maxp : b * maxp + n_pg]
    slot = np.full(T, B, np.int32)
    pos = np.zeros(T, np.int32)
    row = 0
    for b, p0, n in runs:
        if b is not None:
            slot[row : row + n], pos[row : row + n] = b, p0 + np.arange(n)
        row += n
    live = np.arange(maxp)[None, :] * page < ctx[:, None]
    args = (q, kp, vp, kt, vt, jnp.asarray(bt), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray(ctx))
    return args, slot < B, live


def _parent_schedule_fwd(bt_ref, q_ref, kp_ref, vp_ref, kt_ref, vt_ref,
                         slot_c_ref, pos_c_ref, ctx_c_ref, slot_r_ref, pos_r_ref,
                         o_ref, m_s, l_s, acc_s, *, b_slots, maxp, page, g, hd,
                         h_total, scale):
    """Frozen copy of the previous ragged schedule, the reference for the
    tiled one: every (slot, page) grid step scores all T rows, every head,
    over every page of max_len; the in-batch fold scores all T x T rows."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((b == 0) & (j == 0))
    def _init():
        m_s[...] = jnp.full(m_s.shape, -1e30, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    row_b = slot_c_ref[...] == b

    def update(h_i, s, valid, vmat):
        m_old = m_s[:, h_i : h_i + 1]
        l_old = l_s[:, h_i : h_i + 1]
        a_old = acc_s[:, h_i * hd : (h_i + 1) * hd]
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        m_s[:, h_i : h_i + 1] = m_new
        l_s[:, h_i : h_i + 1] = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:, h_i * hd : (h_i + 1) * hd] = a_old * corr + jax.lax.dot_general(
            p, vmat.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def scores(h_i, k):
        return jax.lax.dot_general(
            q_ref[:, h_i * hd : (h_i + 1) * hd], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid_p = row_b & (kv_pos < ctx_c_ref[...]) & (bt_ref[b, j] >= 0)
    for h_i in range(h_total):
        cols = slice((h_i // g) * hd, (h_i // g + 1) * hd)
        update(h_i, scores(h_i, kp_ref[0][:, cols]), valid_p, vp_ref[0][:, cols])

    @pl.when(j == maxp - 1)
    def _in_batch():
        valid = row_b & (slot_r_ref[...] == b) & (pos_r_ref[...] <= pos_c_ref[...])
        for h_i in range(h_total):
            cols = slice((h_i // g) * hd, (h_i // g + 1) * hd)
            update(h_i, scores(h_i, kt_ref[:, cols]), valid, vt_ref[:, cols])

    @pl.when((b == b_slots - 1) & (j == maxp - 1))
    def _finalize():
        for h_i in range(h_total):
            l_h = jnp.maximum(l_s[:, h_i : h_i + 1], 1e-30)
            o_ref[:, h_i * hd : (h_i + 1) * hd] = (
                acc_s[:, h_i * hd : (h_i + 1) * hd] / l_h
            ).astype(o_ref.dtype)


def _parent_schedule(q, kp, vp, kt, vt, bt, slot, pos, ctx):
    """Launch ``_parent_schedule_fwd`` in interpret mode, as the previous
    wrapper did: (B, max_pages) grid, unmapped pages clamped to page 0."""
    t, h, hd = q.shape
    kv = kt.shape[1]
    b, maxp = bt.shape
    page = kp.shape[1]
    kernel = functools.partial(
        _parent_schedule_fwd, b_slots=b, maxp=maxp, page=page, g=h // kv,
        hd=hd, h_total=h, scale=hd**-0.5,
    )

    def whole(shape):
        return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))

    def page_map(bi, ji, bts):
        return (jnp.where(bts[bi, ji] < 0, 0, bts[bi, ji]), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, maxp),
        in_specs=[whole((t, h * hd)),
                  pl.BlockSpec((1, page, kv * hd), page_map),
                  pl.BlockSpec((1, page, kv * hd), page_map),
                  whole((t, kv * hd)), whole((t, kv * hd)),
                  whole((t, 1)), whole((t, 1)), whole((t, 1)),
                  whole((1, t)), whole((1, t))],
        out_specs=whole((t, h * hd)),
        scratch_shapes=[pltpu.VMEM((t, h), jnp.float32),
                        pltpu.VMEM((t, h), jnp.float32),
                        pltpu.VMEM((t, h * hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h * hd), vt.dtype), interpret=True,
    )(bt, q.reshape(t, h * hd), kp.reshape(-1, page, kv * hd),
      vp.reshape(-1, page, kv * hd), kt.reshape(t, kv * hd),
      vt.reshape(t, kv * hd), slot.reshape(t, 1), pos.reshape(t, 1),
      jnp.take(ctx, jnp.clip(slot, 0, b - 1)).reshape(t, 1),
      slot.reshape(1, t), pos.reshape(1, t))
    return out.reshape(t, h, hd)


# ---------------------------------------------------------------------------
# kernel vs reference, dispatch routing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(_BATCHES))
def test_ragged_kernel_matches_ref_interpret(name):
    """Pallas kernel (interpret mode) vs jnp oracle on each ragged batch: pad
    rows are excluded (their output is garbage by contract)."""
    args, real, _ = _mixed_batch(name)
    ref = np.asarray(ragged_attention_ref(*args), np.float32)
    ker = np.asarray(ragged_attention_kernel(*args, interpret=True), np.float32)
    # kernel accumulates fused-f32 while decode-like ref rows round split-bf16,
    # so agreement is to bf16 tolerance, not bitwise
    np.testing.assert_allclose(ker[real], ref[real], atol=0.03, rtol=0.05)


@pytest.mark.parametrize("name", sorted(_BATCHES))
def test_ragged_kernel_matches_parent_schedule(name):
    """The tiled schedule only skips steps that added exactly zero mass, so
    its real rows equal the previous all-rows, all-pages schedule's bit for
    bit, except where a slot's rows span two in-batch key tiles
    (``_KEY_TILE_SPLIT``): there each tile boundary adds one f32
    reassociation, which moves the bf16 output by at most one ulp."""
    args, real, _ = _mixed_batch(name)
    ker = np.asarray(ragged_attention_kernel(*args, interpret=True), np.float32)
    old = np.asarray(_parent_schedule(*args), np.float32)
    if name in _KEY_TILE_SPLIT:
        np.testing.assert_allclose(ker[real], old[real], rtol=2**-7, atol=2**-12)
    else:
        np.testing.assert_array_equal(ker[real], old[real])


@pytest.mark.parametrize("name", sorted(_BATCHES))
def test_ragged_kernel_ignores_dead_pages(name):
    """Every pool page outside the live range of a slot that owns a row is
    NaN: the kernel neither reads those pages into its math nor needs them,
    so its real rows stay finite and unchanged. (The previous schedule
    multiplied every page of max_len, unmapped ones clamped to page 0, by a
    zero probability, and NaN * 0 spread to every row.)"""
    args, real, live = _mixed_batch(name)
    q, kp, vp, kt, vt, bt, slot, pos, ctx = args
    owns = np.isin(np.arange(bt.shape[0]), np.asarray(slot))
    keep = np.unique(np.asarray(bt)[live & owns[:, None]])
    dead = np.setdiff1d(np.arange(kp.shape[0]), keep)
    kp_bad = kp.at[dead].set(jnp.nan)
    vp_bad = vp.at[dead].set(jnp.nan)
    clean = np.asarray(ragged_attention_kernel(*args, interpret=True), np.float32)
    ker = np.asarray(ragged_attention_kernel(
        q, kp_bad, vp_bad, kt, vt, bt, slot, pos, ctx, interpret=True), np.float32)
    assert dead.size > 0
    assert np.isfinite(ker[real]).all()
    np.testing.assert_array_equal(ker[real], clean[real])


def test_ragged_work_counts_tiles_and_live_pages():
    """Hand-built schedule, 16-row tiles, 8-row pages: slot 0 owns row 3
    (tile 0; 17 committed rows = 3 live pages), slot 1 owns rows 14..20
    (tiles 0 and 1; 8 rows = 1 page), slot 2 owns rows 40 and 47 (tile 2;
    ctx 0 = no page), slot 3 owns nothing (its 30 committed rows are not
    read), pads carry slot 4."""
    slot = np.full(48, 4, np.int32)
    slot[3] = 0
    slot[14:21] = 1
    slot[[40, 47]] = 2
    ctx = np.array([17, 8, 0, 30], np.int32)
    rows, pages = ragged_work(slot, ctx, n_slots=4, page=8, tq=16)
    assert rows == 16 + 32 + 16
    assert pages == 3 + 1 + 0
    assert ragged_work(np.full(48, 4, np.int32), ctx, n_slots=4, page=8) == (0, 0)
    # one slot owning every row: one tile loop over all of them
    assert ragged_work(np.zeros(48, np.int32), ctx, n_slots=4, page=8, tq=16) == (48, 3)


def test_dispatch_records_ragged_kind():
    """The routed entry point classifies under kind ``ragged`` and the
    counters distinguish kernel routes from forced-ref routes."""
    args, _, _ = _mixed_batch()
    dispatch.reset_dispatch_counters()
    dispatch.ragged_attention(*args)
    dispatch.ragged_attention(*args, impl="ref")
    c = dispatch.dispatch_counters()
    assert c.get("ragged/kernel") == 1, c
    assert c.get("ragged/ref") == 1 and c.get("ragged/ref[forced]") == 1, c


# ---------------------------------------------------------------------------
# chunk-budget edge cases (prompt vs token_budget boundary)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_prompt", [16, 17])
def test_prompt_at_and_over_budget(dparams, n_prompt):
    """A prompt exactly AT the budget prefills in one launch; one token OVER
    spills a 1-token second chunk. Both must match the bucketed oracle, and
    both compile the same single ragged executable."""
    (prompt,) = _prompts([n_prompt])
    eng = ContinuousBatchingEngine(
        DCFG, dparams, batch_slots=3, max_len=64, paged=True,
        ragged=True, token_budget=16,
    )
    req = Request(jnp.asarray(prompt, jnp.int32), max_new=6)
    eng.serve([req])
    assert req.out == _solo(DCFG, dparams, prompt)
    cs = eng.compile_stats()
    assert cs["ragged_traces"] == 1 and cs["prefill_traces"] == 0, cs


# ---------------------------------------------------------------------------
# the acceptance bar: interleaved token equality vs the bucketed oracle
# ---------------------------------------------------------------------------


def test_ragged_interleaved_token_equality_dense(dparams):
    prompts = _prompts((5, 23, 17, 9))
    oracles = [_solo(DCFG, dparams, p) for p in prompts]
    eng, reqs = _ragged_interleaved(DCFG, dparams, prompts)
    for k, (r, o) in enumerate(zip(reqs, oracles)):
        assert r.out == o, (k, r.out, o)
    cs = assert_compile_budget(eng)
    assert cs["ragged_traces"] == 1 and cs["decode_traces"] == 0, cs


def test_ragged_interleaved_token_equality_moe(mparams):
    """Same bar for the routed-expert family: chunk rows and pad rows flow
    through the capacity-bounded MoE FFN without perturbing token outputs."""
    prompts = _prompts((5, 23, 17, 9))
    oracles = [_solo(MCFG, mparams, p) for p in prompts]
    eng, reqs = _ragged_interleaved(MCFG, mparams, prompts)
    for k, (r, o) in enumerate(zip(reqs, oracles)):
        assert r.out == o, (k, r.out, o)
    assert eng.compile_stats()["ragged_traces"] == 1


def test_engine_counts_attention_work(dparams):
    """``engine.stats`` and each launch's ``StepRecord`` carry what the ragged
    attention computes: ``ragged_work`` of the launch's own row metadata,
    summed over the launches of a run with chunks, decodes and evictions."""
    eng, _ = _ragged_interleaved(DCFG, dparams, _prompts((5, 23, 9)))
    launches = [r for r in eng.step_records if r.slot is not None]
    work = [ragged_work(r.slot, r.ctx, n_slots=eng.batch, page=eng.page_size)
            for r in launches]
    assert [(r.attn_tile_rows, r.attn_live_page_steps) for r in launches] == work
    assert eng.stats["attn_tile_rows"] == sum(w[0] for w in work) > 0
    assert eng.stats["attn_live_page_steps"] == sum(w[1] for w in work) > 0


# ---------------------------------------------------------------------------
# decode latency: admission must not displace decode tokens
# ---------------------------------------------------------------------------


def test_decode_tokens_never_drop_during_admission(dparams):
    """Decode rows are scheduled FIRST, prompt chunks fill what remains: a
    long prompt streaming in over several steps must never cost a live
    decoder its per-step token."""
    eng = ContinuousBatchingEngine(
        DCFG, dparams, batch_slots=3, max_len=64, paged=True,
        ragged=True, token_budget=16,
    )
    steady = [Request(jnp.asarray([7 + k, 11, 13], jnp.int32), max_new=30)
              for k in range(2)]
    for r in steady:
        eng.submit(r)
    eng.step()  # both 3-token prompts prefill inside one budget
    assert all(r._last_logits is not None for r in steady)
    (long_prompt,) = _prompts([40], seed=2)
    burst = Request(jnp.asarray(long_prompt, jnp.int32), max_new=4)
    eng.submit(burst)
    deltas = []
    while burst._last_logits is None:  # burst still prefilling
        before = eng.stats["decode_tokens"]
        eng.step()
        deltas.append(eng.stats["decode_tokens"] - before)
    # 40 prompt tokens through a 16-budget with 2 decode rows reserved:
    # at least 3 admission steps, each still decoding BOTH steady slots
    assert len(deltas) >= 3, deltas
    assert all(d == 2 for d in deltas), deltas


# ---------------------------------------------------------------------------
# compile budget: one executable for the whole lifetime
# ---------------------------------------------------------------------------


def test_ragged_single_trace_no_recompiles(dparams):
    """After the first step's warmup trace, admissions / chunk interleaves /
    evictions all reuse the ONE token-budget-shaped executable — the
    no-recompile sanitizer covers the rest of the lifetime."""
    prompts = _prompts((5, 23, 17, 9))
    eng = ContinuousBatchingEngine(
        DCFG, dparams, batch_slots=3, max_len=64, paged=True,
        ragged=True, token_budget=16,
    )
    reqs = [Request(jnp.asarray(p, jnp.int32), max_new=6) for p in prompts]
    for r in reqs[:2]:
        eng.submit(r)
    eng.step()  # the single warmup trace
    with no_recompiles(eng):
        for r in reqs[2:]:
            eng.submit(r)
        eng.run_until_done()
    cs = assert_compile_budget(eng)
    assert cs["ragged_traces"] == 1, cs
    assert cs["prefill_traces"] == 0 and cs["decode_traces"] == 0, cs


# ---------------------------------------------------------------------------
# loud failure modes
# ---------------------------------------------------------------------------


def test_ragged_without_paged_falls_back_with_warning(dparams):
    with pytest.warns(UserWarning, match="ragged"):
        eng = ContinuousBatchingEngine(
            DCFG, dparams, batch_slots=2, max_len=64, ragged=True
        )
    assert not eng.ragged
    # the fallback engine still serves correctly through the bucketed path
    (prompt,) = _prompts([7])
    req = Request(jnp.asarray(prompt, jnp.int32), max_new=4)
    eng.serve([req])
    assert req.out == _solo(DCFG, dparams, prompt, max_new=4)


def test_ragged_token_budget_validation(dparams):
    """A budget smaller than the slot count cannot even fit one decode row
    per slot: rejected at construction, not wedged at runtime."""
    with pytest.raises(ValueError, match="token_budget"):
        ContinuousBatchingEngine(
            DCFG, dparams, batch_slots=4, max_len=64, paged=True,
            ragged=True, token_budget=2,
        )
