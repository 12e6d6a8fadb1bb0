"""Ragged paged attention — one launch for a mixed prefill/decode token batch.

The serving engine's unified step concatenates every live request's scheduled
tokens (prefill chunks + one decode token per slot) into a single flat batch
of ``T = token_budget`` rows. Each row ``t`` belongs to engine slot
``slot[t]`` (``slot == B`` marks padding), sits at absolute position
``pos[t]`` in that slot's timeline, and must attend

* the slot's **committed cache prefix** ``[0, ctx[slot[t]])`` — rows written
  by previous steps, living in the PR-4 page pools behind the slot's block
  table, and
* the **in-batch prefix**: rows ``u`` of the same batch with
  ``slot[u] == slot[t]`` and ``pos[u] <= pos[t]`` (causal within the row's
  span, including itself).

The kernel is an online-softmax (flash-attention recurrence) sweep over a
``(B, max_pages)`` grid in which each grid step does only its slot's work.
The wrapper computes per slot, with plain jnp, the first and last row it
owns (``lo[b]``, ``hi[b]``) and its committed length ``ctx[b]`` (0 for a
slot that owns no row), and scalar-prefetches them beside the block table.
Grid step ``(b, j)``:

* computes page ``j`` of slot ``b`` only while the page holds committed
  keys (``j * page < ctx[b]``), and only over the query tiles of ``TQ``
  rows that cover ``[lo[b], hi[b]]``. The K/V index maps clamp ``j`` to the
  slot's last live page, so the steps past it repeat the block index and
  the pipeline fetches nothing new; unmapped (-1) entries clamp to page 0,
  which is never computed on;
* at the slot's LAST page step (``j == max_pages - 1``) also folds in the
  in-batch rows from the resident K/V panels: the same query tiles against
  the key tiles (up to 128 rows each) that cover ``[lo[b], hi[b]]``. The
  in-batch fold rides the final page iteration instead of spending a grid
  step of its own.

In the ragged engine a slot's rows are one contiguous run (one decode row
or one prompt chunk), but the kernel stays correct for any layout: within a
tile, rows of other slots are masked (``slot == b``) and inert — ``m`` does
not move, the correction factor is ``exp(0) = 1`` and their probability mass
is zero. The steps the span and live-page bounds skip only ever added that
zero mass, so a row's arithmetic is that of the all-rows, all-pages sweep,
except for one f32 reassociation per in-batch key-tile boundary when a
slot's rows span two key tiles (T > 128). Pages outside a slot's live range
are never read into the math: they may hold anything, NaN included. State
is initialised once per call over all rows and the output written once, at
the last grid step; ``ragged_work`` counts, on the host, the rows and page
steps a call computes.

Numerics: the jnp reference (``ragged_attention_ref``) mirrors each row's
bucketed-engine counterpart rounding-for-rounding — decode rows follow
``models/common.attention_decode_ro`` (cache and self value dots rounded to
bf16 separately), prefill-chunk rows follow ``_sdpa``'s single fused dot
(f32 partial accumulation, one final bf16 rounding). Single-chunk prompts
and decode steps are then bit-identical to the bucketed engine. A prompt
split across MULTIPLE chunks has exactly one f32 reassociation at each
chunk boundary (cache-sum + in-batch-sum vs the oracle's one sequential
sum); in practice greedy outputs stay token-identical (the serving tests
pin such workloads), but a ~1e-7-relative perturbation landing on a bf16
rounding boundary can in principle flip a near-tied argmax. The Pallas
kernel always accumulates fused-f32; agreement with the ref is tested to
bf16 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contracts import validate_ragged_attention


__all__ = ["TQ", "ragged_attention_kernel", "ragged_attention_ref", "ragged_work"]

_NEG_INF = -1e30
# Query rows per tile: one packed bf16 sublane tile (16, 128), so a tile's
# q slice needs no relayout and a decode row costs 16 rows, not T.
TQ = 16
# In-batch keys per tile: one lane-dense (TQ, 128) score tile; a 256-row
# prompt chunk folds its own rows in two key tiles.
_TK_MAX = 128


def ragged_attention_ref(q, kp, vp, kt, vt, bt, slot, pos, ctx):
    """jnp oracle for the ragged step's attention.

    q (T, H, hd) / kt, vt (T, KV, hd): this step's post-RoPE rows.
    kp, vp (P, page, KV, hd): one layer's paged K/V pools.
    bt (B, maxp) int32 block tables, slot/pos (T,) int32 row metadata
    (slot == B is padding), ctx (B,) int32 committed rows per slot.
    Returns (T, H, hd) in vt.dtype; pad rows are garbage (caller discards).
    """
    t, h, hd = q.shape
    kv = kt.shape[1]
    g = h // kv
    b, maxp = bt.shape
    page = kp.shape[1]
    s_max = maxp * page
    slot_c = jnp.clip(slot, 0, b - 1)

    # dense per-row cache view through the block tables (unmapped -> page 0,
    # masked below by the ctx prefix — same contract as common.gather_pages)
    kc = kp[jnp.maximum(bt, 0)].reshape(b, s_max, kv, hd)[slot_c]  # (T, S, KV, hd)
    vc = vp[jnp.maximum(bt, 0)].reshape(b, s_max, kv, hd)[slot_c]

    qg = q.reshape(t, kv, g, hd)
    real = slot < b  # (T,)

    # committed-cache scores, mirroring attention_decode_ro: bf16 einsum,
    # cast f32, scale, strict prefix mask
    logits_c = jnp.einsum("tkgh,tskh->tkgs", qg, kc).astype(jnp.float32)
    logits_c = logits_c / (hd**0.5)
    mask_c = (jnp.arange(s_max)[None, :] < ctx[slot_c][:, None]) & real[:, None]
    logits_c = jnp.where(mask_c[:, None, None, :], logits_c, _NEG_INF)

    # in-batch scores: same-slot causal prefix (includes self)
    logits_b = jnp.einsum("tkgh,ukh->tkgu", qg, kt).astype(jnp.float32)
    logits_b = logits_b / (hd**0.5)
    mask_b = (slot[None, :] == slot[:, None]) & (pos[None, :] <= pos[:, None])
    mask_b = mask_b & real[:, None]
    logits_b = jnp.where(mask_b[:, None, None, :], logits_b, _NEG_INF)

    m = jnp.maximum(
        jnp.max(logits_c, axis=-1, keepdims=True),
        jnp.max(logits_b, axis=-1, keepdims=True),
    )
    pc = jnp.exp(logits_c - m)
    pb = jnp.exp(logits_b - m)
    den = jnp.sum(pc, axis=-1, keepdims=True) + jnp.sum(pb, axis=-1, keepdims=True)
    # value reduction, matching each row's BUCKETED-engine counterpart
    # rounding-for-rounding so greedy decoding stays token-identical:
    # * decode rows (exactly one in-batch term: themselves) mirror
    #   attention_decode_ro — cache and self dots rounded to bf16 separately,
    #   then added in bf16;
    # * prefill-chunk rows (>= 2 in-batch terms) mirror _sdpa's single fused
    #   dot — both partial dots accumulate in f32 and round ONCE, otherwise
    #   the extra bf16 rounding drifts a full ulp off the bucketed oracle.
    pcd = (pc / den).astype(vc.dtype)
    pbd = (pb / den).astype(vt.dtype)
    out_fused = jnp.einsum("tkgs,tskh->tkgh", pcd, vc,
                           preferred_element_type=jnp.float32)
    out_fused = out_fused + jnp.einsum("tkgu,ukh->tkgh", pbd, vt,
                                       preferred_element_type=jnp.float32)
    out_split = (jnp.einsum("tkgs,tskh->tkgh", pcd, vc)
                 + jnp.einsum("tkgu,ukh->tkgh", pbd, vt))
    decode_like = (jnp.sum(mask_b, axis=-1) <= 1)[:, None, None, None]
    out = jnp.where(decode_like, out_split.astype(jnp.float32), out_fused)
    return out.astype(vt.dtype).reshape(t, h, hd)


def _tiles(t: int) -> tuple[int, int]:
    """(key tile rows, padded row count) for a batch of ``t`` rows: query
    tiles are TQ rows, key tiles up to _TK_MAX, and the padded count is a
    multiple of both."""
    tk = min(_TK_MAX, -(-t // TQ) * TQ)
    return tk, -(-t // tk) * tk


def ragged_work(slot, ctx, *, n_slots: int, page: int, tq: int = TQ) -> tuple[int, int]:
    """What one kernel call computes for this schedule (host-side, numpy).

    Slot ``b`` owning rows ``lo..hi`` of ``slot`` computes the ``tq``-row
    query tiles ``lo // tq .. hi // tq`` on each of its ``ceil(ctx[b] /
    page)`` live pages; a slot that owns no row computes nothing. Returns
    ``(tile_rows, live_page_steps)``: the tile-rounded spans and the live
    pages, each summed over the slots that own a row."""
    slot = np.asarray(slot)
    rows = np.flatnonzero((slot >= 0) & (slot < n_slots))
    if rows.size == 0:
        return 0, 0
    owners = slot[rows]
    b, first = np.unique(owners, return_index=True)
    _, last = np.unique(owners[::-1], return_index=True)
    lo, hi = rows[first], rows[rows.size - 1 - last]
    tile_rows = int(np.sum(hi // tq - lo // tq + 1)) * tq
    return tile_rows, int(np.sum(-(-np.asarray(ctx)[b] // page)))


def _ragged_attention_fwd(
    # scalar prefetch, (B,) int32 each unless noted
    bt_ref,  # (B, maxp) — block tables, clamped to each slot's last live page
    lo_ref,  # first row the slot owns (T_pad when it owns none)
    hi_ref,  # last row the slot owns (-1 when it owns none)
    ctx_ref,  # committed rows (0 for a slot that owns no row)
    # inputs, tile-major: [i] is query tile i (TQ rows) or key tile i (tk rows)
    q_ref,  # (nq, TQ, H*hd) bf16 — whole panel, resident
    kp_ref,  # (1, page, KV*hd) bf16 — one K page, streamed via bt
    vp_ref,  # (1, page, KV*hd) bf16 — one V page, streamed via bt
    kt_ref,  # (nk, tk, KV*hd) bf16 — in-batch K rows, resident
    vt_ref,  # (nk, tk, KV*hd) bf16 — in-batch V rows, resident
    slot_c_ref,  # (nq, TQ, 1) int32 — row -> slot (column layout)
    pos_c_ref,  # (nq, TQ, 1) int32 — row -> absolute position
    slot_r_ref,  # (nk, 1, tk) int32 — slot again, row layout (avoids transposes)
    pos_r_ref,  # (nk, 1, tk) int32
    # output
    o_ref,  # (nq, TQ, H*hd) bf16
    # scratch (persist across the sequential grid)
    m_s,  # (nq, TQ, H) f32 — running max
    l_s,  # (nq, TQ, H) f32 — running denominator
    acc_s,  # (nq, TQ, H*hd) f32 — running numerator
    *,
    b_slots: int,
    maxp: int,
    page: int,
    tk: int,
    g: int,
    hd: int,
    h_total: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((b == 0) & (j == 0))
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    lo, hi, n_ctx = lo_ref[b], hi_ref[b], ctx_ref[b]
    q0 = lo // TQ  # the slot's first query tile
    n_q = jnp.where(hi >= lo, hi // TQ - q0 + 1, 0)

    def update(qi, h_i, s, valid, vmat):
        # one online-softmax fold for head h_i into query tile qi: s (TQ, S')
        # raw f32 scores, valid (TQ, S') mask, vmat (S', hd) values
        m_old = m_s[qi, :, h_i : h_i + 1]
        l_old = l_s[qi, :, h_i : h_i + 1]
        a_old = acc_s[qi, :, h_i * hd : (h_i + 1) * hd]
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        m_s[qi, :, h_i : h_i + 1] = m_new
        l_s[qi, :, h_i : h_i + 1] = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_s[qi, :, h_i * hd : (h_i + 1) * hd] = a_old * corr + jax.lax.dot_general(
            p, vmat.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def attend(qi, valid, k_blk, v_blk):
        # fold one key block (refs of (S', KV*hd)) into query tile qi, every head
        for h_i in range(h_total):
            cols = slice((h_i // g) * hd, (h_i // g + 1) * hd)
            s = jax.lax.dot_general(
                q_ref[qi, :, h_i * hd : (h_i + 1) * hd], k_blk[:, cols],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale
            update(qi, h_i, s, valid, v_blk[:, cols])

    # committed prefix: page j of slot b, only while it holds committed keys.
    # Past the last live page the index maps repeat the previous block, so
    # those steps start no DMA either
    @pl.when((j * page < n_ctx) & (bt_ref[b, j] >= 0))
    def _page():
        kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        live = kv_pos < n_ctx  # (1, page)

        def tile(i, carry):
            qi = q0 + i
            attend(qi, (slot_c_ref[qi] == b) & live, kp_ref.at[0], vp_ref.at[0])
            return carry

        jax.lax.fori_loop(0, n_q, tile, 0)

    @pl.when(j == maxp - 1)
    def _in_batch():
        # this step's own rows: same-slot causal prefix, including self, over
        # the key tiles that cover the slot's rows. Folded into the slot's
        # LAST page step — the in-batch tile costs no extra grid iteration
        k0 = lo // tk
        n_k = jnp.where(hi >= lo, hi // tk - k0 + 1, 0)

        def tile(i, carry):
            qi = q0 + i
            row_b = slot_c_ref[qi] == b
            pos_q = pos_c_ref[qi]

            def key_tile(kk, carry):
                ki = k0 + kk
                valid = row_b & (slot_r_ref[ki] == b) & (pos_r_ref[ki] <= pos_q)
                attend(qi, valid, kt_ref.at[ki], vt_ref.at[ki])
                return carry

            return jax.lax.fori_loop(0, n_k, key_tile, carry)

        jax.lax.fori_loop(0, n_q, tile, 0)

    @pl.when((b == b_slots - 1) & (j == maxp - 1))
    def _finalize():
        # pad rows have l == 0 (never valid anywhere) -> guarded divide;
        # their garbage output is discarded host-side
        for h_i in range(h_total):
            l_h = jnp.maximum(l_s[:, :, h_i : h_i + 1], 1e-30)
            o_ref[:, :, h_i * hd : (h_i + 1) * hd] = (
                acc_s[:, :, h_i * hd : (h_i + 1) * hd] / l_h
            ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_attention_kernel(q, kp, vp, kt, vt, bt, slot, pos, ctx, *,
                            interpret: bool = False):
    """Pallas launch wrapper; same signature/semantics as the ref."""
    t, h, hd = q.shape
    kv = kt.shape[1]
    g = h // kv
    b, maxp = bt.shape
    page = kp.shape[1]
    tk, t_pad = _tiles(t)
    validate_ragged_attention(t_pad, h, kv, hd, b, maxp, page)
    nq, nk = t_pad // TQ, t_pad // tk

    # each slot's row span and committed length; a slot that owns no row
    # gets ctx 0, so it computes no page (and fetches one at most)
    slot = slot.astype(jnp.int32)
    owner = slot[None, :] == jnp.arange(b, dtype=jnp.int32)[:, None]  # (B, T)
    rows = jnp.arange(t, dtype=jnp.int32)
    lo = jnp.min(jnp.where(owner, rows, t_pad), axis=1)
    hi = jnp.max(jnp.where(owner, rows, -1), axis=1)
    ctx_b = jnp.where(hi >= lo, ctx.astype(jnp.int32), 0)
    # the K/V index maps read page j of slot b through this table, whose
    # columns past the slot's last live page repeat that page: the block
    # index then stays put and the pipeline fetches nothing new. Unmapped
    # (-1) entries fetch page 0, which is never computed on
    last = jnp.maximum((ctx_b + page - 1) // page - 1, 0)
    cols = jnp.minimum(jnp.arange(maxp, dtype=jnp.int32)[None, :], last[:, None])
    bt_live = jnp.take_along_axis(bt.astype(jnp.int32), cols, axis=1)

    def pad(x, fill):  # rows up to t_pad; pad rows carry slot B
        if t_pad == t:
            return x
        return jnp.pad(x, [(0, t_pad - t)] + [(0, 0)] * (x.ndim - 1),
                       constant_values=fill)

    q3 = pad(q, 0).reshape(nq, TQ, h * hd)
    kt3 = pad(kt, 0).reshape(nk, tk, kv * hd)
    vt3 = pad(vt, 0).reshape(nk, tk, kv * hd)
    slot_p, pos_p = pad(slot, b), pad(pos.astype(jnp.int32), 0)
    kp2 = kp.reshape(kp.shape[0], page, kv * hd)
    vp2 = vp.reshape(vp.shape[0], page, kv * hd)

    def whole(shape):
        return pl.BlockSpec(shape, lambda *_: (0, 0, 0))

    page_spec = pl.BlockSpec(
        (1, page, kv * hd),
        lambda bi, ji, bts, *_: (
            jnp.where(bts[bi, ji] < 0, 0, bts[bi, ji]), 0, 0
        ),
    )

    kernel = functools.partial(
        _ragged_attention_fwd,
        b_slots=b, maxp=maxp, page=page, tk=tk, g=g, hd=hd, h_total=h,
        scale=hd**-0.5,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, maxp),
        in_specs=[
            whole((nq, TQ, h * hd)),
            page_spec,
            page_spec,
            whole((nk, tk, kv * hd)),
            whole((nk, tk, kv * hd)),
            whole((nq, TQ, 1)),
            whole((nq, TQ, 1)),
            whole((nk, 1, tk)),
            whole((nk, 1, tk)),
        ],
        out_specs=whole((nq, TQ, h * hd)),
        scratch_shapes=[
            pltpu.VMEM((nq, TQ, h), jnp.float32),
            pltpu.VMEM((nq, TQ, h), jnp.float32),
            pltpu.VMEM((nq, TQ, h * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, TQ, h * hd), vt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY)
        ),
        interpret=interpret,
        name="ragged_attention",
    )(bt_live, lo, hi, ctx_b, q3, kp2, vp2, kt3, vt3,
      slot_p.reshape(nq, TQ, 1), pos_p.reshape(nq, TQ, 1),
      slot_p.reshape(nk, 1, tk), pos_p.reshape(nk, 1, tk))
    return out.reshape(t_pad, h, hd)[:t]
