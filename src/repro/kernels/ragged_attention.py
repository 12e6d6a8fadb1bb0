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
``(B, max_pages)`` grid. Every grid step ``(b, j)`` streams one K/V page
pair of slot ``b`` — the page index comes straight from the
scalar-prefetched block table via the BlockSpec index map, so unmapped (-1)
entries clamp to page 0 and are masked in-kernel. The LAST page step per
slot (``j == max_pages - 1``) additionally folds in the in-batch rows from
the resident ``(T, KV*hd)`` K/V panels — the in-batch tile rides the final
page iteration instead of spending a grid step of its own, so the sweep is
``B * max_pages`` steps, not ``B * (max_pages + 1)``. Rows not belonging to
the current slot are naturally inert: their masks are all-False, so ``m``
does not move, the correction factor is ``exp(0) = 1`` and their
probability mass is zero — the scratch state needs no explicit row gating.
Output is written once, at the last grid step.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contracts import validate_ragged_attention


__all__ = ["ragged_attention_kernel", "ragged_attention_ref"]

_NEG_INF = -1e30


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


def _ragged_attention_fwd(
    # scalar prefetch
    bt_ref,  # (B, maxp) int32 — block tables, read by index maps + validity
    # inputs
    q_ref,  # (T, H*hd)  bf16 — whole panel, resident
    kp_ref,  # (1, page, KV*hd) bf16 — one K page, streamed via bt
    vp_ref,  # (1, page, KV*hd) bf16 — one V page, streamed via bt
    kt_ref,  # (T, KV*hd) bf16 — in-batch K rows, resident
    vt_ref,  # (T, KV*hd) bf16 — in-batch V rows, resident
    slot_c_ref,  # (T, 1) int32 — row -> slot (column layout)
    pos_c_ref,  # (T, 1) int32 — row -> absolute position
    ctx_c_ref,  # (T, 1) int32 — row -> committed prefix length
    slot_r_ref,  # (1, T) int32 — slot again, row layout (avoids transposes)
    pos_r_ref,  # (1, T) int32
    # output
    o_ref,  # (T, H*hd) bf16
    # scratch (persist across the sequential grid)
    m_s,  # (T, H) f32 — running max
    l_s,  # (T, H) f32 — running denominator
    acc_s,  # (T, H*hd) f32 — running numerator
    *,
    b_slots: int,
    maxp: int,
    page: int,
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

    row_b = slot_c_ref[...] == b  # (T, 1): rows owned by the current slot

    def update(h_i, s, valid, vmat):
        # one online-softmax fold for head h_i: s (T, S') raw f32 scores,
        # valid (T, S') mask, vmat (S', hd) values
        m_old = m_s[:, h_i : h_i + 1]
        l_old = l_s[:, h_i : h_i + 1]
        a_old = acc_s[:, h_i * hd : (h_i + 1) * hd]
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        m_s[:, h_i : h_i + 1] = m_new
        l_s[:, h_i : h_i + 1] = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:, h_i * hd : (h_i + 1) * hd] = a_old * corr + jax.lax.dot_general(
            p, vmat.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # committed prefix: one page of slot b's cache per grid step (fetched
    # through the block table by the BlockSpec index map; -1 clamps to page
    # 0 and is masked here) — every (b, j) step is a page step
    page_ok = bt_ref[b, j] >= 0
    kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid_p = row_b & (kv_pos < ctx_c_ref[...]) & page_ok  # (T, page)
    for h_i in range(h_total):
        kv_i = h_i // g
        qh = q_ref[:, h_i * hd : (h_i + 1) * hd]  # (T, hd)
        kh = kp_ref[0][:, kv_i * hd : (kv_i + 1) * hd]  # (page, hd)
        s = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        update(h_i, s, valid_p, vp_ref[0][:, kv_i * hd : (kv_i + 1) * hd])

    @pl.when(j == maxp - 1)
    def _in_batch():
        # this step's own rows: same-slot causal prefix, including self.
        # Folded into the slot's LAST page step — the in-batch tile costs no
        # extra grid iteration
        valid = row_b & (slot_r_ref[...] == b) & (pos_r_ref[...] <= pos_c_ref[...])
        for h_i in range(h_total):
            kv_i = h_i // g
            qh = q_ref[:, h_i * hd : (h_i + 1) * hd]
            kh = kt_ref[:, kv_i * hd : (kv_i + 1) * hd]  # (T, hd)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            update(h_i, s, valid, vt_ref[:, kv_i * hd : (kv_i + 1) * hd])

    @pl.when((b == b_slots - 1) & (j == maxp - 1))
    def _finalize():
        # pad rows have l == 0 (never valid anywhere) -> guarded divide;
        # their garbage output is discarded host-side
        for h_i in range(h_total):
            l_h = jnp.maximum(l_s[:, h_i : h_i + 1], 1e-30)
            o_ref[:, h_i * hd : (h_i + 1) * hd] = (
                acc_s[:, h_i * hd : (h_i + 1) * hd] / l_h
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
    validate_ragged_attention(t, h, kv, hd, b, maxp, page)

    q2 = q.reshape(t, h * hd)
    kp2 = kp.reshape(kp.shape[0], page, kv * hd)
    vp2 = vp.reshape(vp.shape[0], page, kv * hd)
    kt2 = kt.reshape(t, kv * hd)
    vt2 = vt.reshape(t, kv * hd)
    slot_c = slot.astype(jnp.int32).reshape(t, 1)
    pos_c = pos.astype(jnp.int32).reshape(t, 1)
    ctx_c = jnp.take(ctx.astype(jnp.int32), jnp.clip(slot, 0, b - 1)).reshape(t, 1)
    slot_r = slot.astype(jnp.int32).reshape(1, t)
    pos_r = pos.astype(jnp.int32).reshape(1, t)

    kernel = functools.partial(
        _ragged_attention_fwd,
        b_slots=b, maxp=maxp, page=page, g=g, hd=hd, h_total=h,
        scale=hd**-0.5,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((t, h * hd), lambda bi, ji, bts: (0, 0)),
            # the page index comes from the scalar-prefetched block table:
            # unmapped (-1) entries clamp to page 0 (masked in-kernel); the
            # in-batch tile shares the last page step, so ji is always a
            # real page column
            pl.BlockSpec(
                (1, page, kv * hd),
                lambda bi, ji, bts: (
                    jnp.where(bts[bi, ji] < 0, 0, bts[bi, ji]), 0, 0
                ),
            ),
            pl.BlockSpec(
                (1, page, kv * hd),
                lambda bi, ji, bts: (
                    jnp.where(bts[bi, ji] < 0, 0, bts[bi, ji]), 0, 0
                ),
            ),
            pl.BlockSpec((t, kv * hd), lambda bi, ji, bts: (0, 0)),
            pl.BlockSpec((t, kv * hd), lambda bi, ji, bts: (0, 0)),
            pl.BlockSpec((t, 1), lambda bi, ji, bts: (0, 0)),
            pl.BlockSpec((t, 1), lambda bi, ji, bts: (0, 0)),
            pl.BlockSpec((t, 1), lambda bi, ji, bts: (0, 0)),
            pl.BlockSpec((1, t), lambda bi, ji, bts: (0, 0)),
            pl.BlockSpec((1, t), lambda bi, ji, bts: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, h * hd), lambda bi, ji, bts: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t, h), jnp.float32),
            pltpu.VMEM((t, h), jnp.float32),
            pltpu.VMEM((t, h * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h * hd), vt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY)
        ),
        interpret=interpret,
        name="ragged_attention",
    )(bt.astype(jnp.int32), q2, kp2, vp2, kt2, vt2,
      slot_c, pos_c, ctx_c, slot_r, pos_r)
    return out.reshape(t, h, hd)
