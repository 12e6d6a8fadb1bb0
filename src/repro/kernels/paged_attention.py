"""Block-table paged decode attention — page-table indirection inside the
kernel, with the new tokens' KV scatter fused into the epilogue.

The PR-4 paged runtime used to materialize a dense ``(B, max_len, KV, hd)``
view of each slot's pages (``models/common.gather_pages``) before the decode
attention math, burning HBM bandwidth on every mapped page whether or not the
slot's prefix reaches it — and then issued a separate scatter to commit the
new token's K/V into the tail page. This kernel removes both:

* the block table is **scalar-prefetched** (``PrefetchScalarGridSpec``), so
  each grid step's BlockSpec index map fetches exactly one K/V page pair of
  the current slot straight from the pool — unmapped (-1) entries clamp to
  page 0 and are masked in-kernel;
* attention is an **online-softmax** (flash recurrence) sweep over the pages
  with f32 scratch, exactly like ``ragged_attention.py``;
* each slot contributes ``sq <= DECODE_M_MAX`` **query rows** (speculative
  verification stacks K draft tokens per slot), attending the committed
  prefix ``[0, pos_b)`` plus the earlier draft rows of the same slot
  (in-batch causal, including self);
* with ``commit=True`` the epilogue **scatters the new K/V rows into the
  slot's tail page(s) in the same launch**: the pool arrays are aliased
  input->output (``input_output_aliases``), the tail pages are streamed in
  during the two epilogue grid steps, copied through VMEM with the new rows
  folded in, and flushed back — no separate scatter launch, and only the
  tail pages are rewritten.

Grid: ``(B, max_pages + 2)`` (``+ 1`` without commit). Steps ``j <
max_pages`` stream cache pages; step ``j == max_pages`` folds the in-batch
rows and rewrites tail page 0; step ``j == max_pages + 1`` rewrites tail
page 1 (the draft span may straddle a page boundary). The output panel is
written once, at the last grid step.

Numerics: the jnp reference (:func:`paged_decode_ref`) reproduces the
sequential bucketed decode (``models/common.attention_decode_ro``)
rounding-for-rounding — it overwrites the dense cache view's rows at
``pos_b + i`` with the draft K/V (bf16, exactly the values a sequential
engine would have committed), computes one bf16-rounded cache dot per row
with a strict per-row prefix mask, and adds the separately-rounded self
term. Verification logits for row ``i`` are therefore bit-identical to what
the non-speculative engine would produce at position ``pos_b + i``, which
is what makes greedy speculative acceptance exact. The Pallas kernel
accumulates fused-f32 (flash recurrence); agreement with the ref is tested
to bf16 tolerance.

Commit-mode aliasing caveat: slots whose tail page is unmapped (idle slots
decoding garbage in lock-step: ``bt`` all -1) clamp their tail stream to
page 0 and flush back an unmodified copy of it. That copy is fetched and
flushed within the same slot's grid steps, so it is benign unless page 0 is
simultaneously the *valid* tail of a later slot in the same launch — callers
using ``commit=True`` should pass batches whose live slots all have mapped
tails (the serving engine's scan path commits post-scan instead and is not
affected).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contracts import validate_paged_decode


__all__ = ["paged_decode_kernel", "paged_decode_ref", "scatter_rows_pool"]

_NEG_INF = -1e30


def scatter_rows_pool(pool, t, bt, slot, pos):
    """Scatter flat rows into a single-layer page pool (the ref's commit).

    pool (P, page, KV, hd), t (R, KV, hd), bt (B, maxp), slot/pos (R,).
    Row ``i`` lands in page ``bt[slot_i, pos_i // page]`` at offset
    ``pos_i % page``; rows past the block table or into unmapped pages are
    dropped through the ``n_pages`` OOB sentinel (NOT -1, which would wrap
    into the last page — same rule as ``models/common.scatter_rows_pages``).
    """
    page = pool.shape[1]
    n_pages = pool.shape[0]
    b, maxp = bt.shape
    pi = pos // page
    page_id = bt[jnp.clip(slot, 0, b - 1), jnp.minimum(pi, maxp - 1)]
    ok = (slot < b) & (pi < maxp) & (page_id >= 0)
    page_id = jnp.where(ok, page_id, n_pages)
    return pool.at[page_id, pos % page].set(t.astype(pool.dtype), mode="drop")


def paged_decode_ref(q, kp, vp, kt, vt, bt, pos, *, commit: bool = True):
    """jnp oracle for paged multi-query decode attention.

    q (B, sq, H, hd) / kt, vt (B, sq, KV, hd): post-RoPE draft rows — row
    ``i`` of slot ``b`` sits at absolute position ``pos[b] + i``.
    kp, vp (P, page, KV, hd): one layer's paged K/V pools.
    bt (B, maxp) int32 block tables (-1 unmapped), pos (B,) int32 committed
    prefix lengths. Returns ``(out, kp_new, vp_new)`` with the draft rows
    committed to their tail pages, or just ``out`` when ``commit=False``.

    Numerics mirror ``models/common.attention_decode_ro`` per row: the dense
    cache view (with draft rows scattered in at their future positions) goes
    through ONE bf16-rounded value dot under a strict per-row prefix mask,
    the self term is rounded separately, and the two add in bf16 — so
    ``sq == 1`` is bit-identical to the pre-existing gather_pages decode
    path, and row ``i`` of a draft stack is bit-identical to what a
    sequential engine would compute at position ``pos[b] + i``.
    """
    b, sq, h, hd = q.shape
    kv = kt.shape[2]
    g = h // kv
    maxp = bt.shape[1]
    page = kp.shape[1]
    s_max = maxp * page

    # dense per-slot cache view (unmapped -> page 0, masked below), then
    # overwrite the draft span: the view now holds exactly the rows a
    # sequential engine's cache would hold at each verified position
    kc = kp[jnp.maximum(bt, 0)].reshape(b, s_max, kv, hd)
    vc = vp[jnp.maximum(bt, 0)].reshape(b, s_max, kv, hd)
    rows = pos[:, None].astype(jnp.int32) + jnp.arange(sq, dtype=jnp.int32)[None, :]
    ridx = jnp.where(rows < s_max, rows, s_max)  # OOB rows drop
    bi = jnp.arange(b)[:, None]
    kc = kc.at[bi, ridx].set(kt.astype(kc.dtype), mode="drop")
    vc = vc.at[bi, ridx].set(vt.astype(vc.dtype), mode="drop")

    qg = q.reshape(b, sq, kv, g, hd)
    logits_c = jnp.einsum("bskgh,btkh->bkgst", qg, kc).astype(jnp.float32)
    logits_c = logits_c / (hd**0.5)
    # strict per-ROW prefix mask: row i sees the committed prefix plus the
    # earlier draft rows (which now live in the view at pos_b..pos_b+i-1)
    mask = jnp.arange(s_max)[None, None, :] < rows[:, :, None]  # (B, sq, S)
    logits_c = jnp.where(mask[:, None, None, :, :], logits_c, _NEG_INF)
    logit_s = jnp.einsum("bskgh,bskh->bkgs", qg, kt).astype(jnp.float32)[..., None]
    logit_s = logit_s / (hd**0.5)
    m = jnp.maximum(jnp.max(logits_c, axis=-1, keepdims=True), logit_s)
    pc = jnp.exp(logits_c - m)
    ps = jnp.exp(logit_s - m)
    den = jnp.sum(pc, axis=-1, keepdims=True) + ps
    out = jnp.einsum("bkgst,btkh->bskgh", (pc / den).astype(vc.dtype), vc)
    self_w = (ps / den)[..., 0][..., None].transpose(0, 3, 1, 2, 4).astype(vt.dtype)
    out = out + self_w * vt[:, :, :, None, :]
    out = out.reshape(b, sq, h, hd)
    if not commit:
        return out
    slot_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), sq)
    kp_new = scatter_rows_pool(kp, kt.reshape(b * sq, kv, hd), bt, slot_ids, rows.reshape(-1))
    vp_new = scatter_rows_pool(vp, vt.reshape(b * sq, kv, hd), bt, slot_ids, rows.reshape(-1))
    return out, kp_new, vp_new


def _fold(m_s, l_s, acc_s, h_i, hd, s, valid, vmat):
    """One online-softmax fold for head ``h_i``: s (T, S') raw f32 scores,
    valid (T, S') mask, vmat (S', hd) values. All-False rows are inert
    (``m`` stays, corr = exp(0) = 1, zero mass)."""
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


def _paged_decode_fwd(
    # scalar prefetch
    bt_ref,  # (B, maxp) int32 — block tables, read by index maps + validity
    tails_ref,  # (B, 2) int32 — clamped tail PAGE ids (epilogue streams)
    posp_ref,  # (B,) int32 — committed prefix lengths (epilogue offsets)
    # inputs
    q_ref,  # (B*sq, H*hd) bf16 — whole query panel, resident
    kp_ref,  # (1, page, KV*hd) bf16 — one K page, streamed via bt / tails
    vp_ref,  # (1, page, KV*hd) bf16 — one V page, streamed via bt / tails
    kt_ref,  # (B*sq, KV*hd) bf16 — draft K rows, resident
    vt_ref,  # (B*sq, KV*hd) bf16 — draft V rows, resident
    kslot_ref,  # (B, sq, KV*hd) bf16 — the same draft K rows, slot-major
    vslot_ref,  # (B, sq, KV*hd) bf16
    pos_c_ref,  # (B*sq, 1) int32 — per-row committed prefix length
    # outputs
    o_ref,  # (B*sq, H*hd) bf16
    kp_o_ref,  # (1, page, KV*hd) bf16 — tail page write-back (aliased to kp)
    vp_o_ref,  # (1, page, KV*hd) bf16 — tail page write-back (aliased to vp)
    # scratch (persist across the sequential grid)
    m_s,  # (B*sq, H) f32
    l_s,  # (B*sq, H) f32
    acc_s,  # (B*sq, H*hd) f32
    *,
    b_slots: int,
    sq: int,
    maxp: int,
    page: int,
    g: int,
    hd: int,
    h_total: int,
    scale: float,
    commit: bool,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    t2 = b_slots * sq
    last_j = maxp + 1 if commit else maxp

    @pl.when((b == 0) & (j == 0))
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    rid = jax.lax.broadcasted_iota(jnp.int32, (t2, 1), 0)  # row index column
    row_b = (rid // sq) == b  # (T2, 1): rows owned by the current slot

    @pl.when(j < maxp)
    def _cache_page():
        # committed prefix: one page of slot b's cache (fetched through the
        # block table by the BlockSpec index map; -1 clamps to page 0 and is
        # masked here)
        page_ok = bt_ref[b, j] >= 0
        kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        valid = row_b & (kv_pos < pos_c_ref[...]) & page_ok  # (T2, page)
        for h_i in range(h_total):
            kv_i = h_i // g
            qh = q_ref[:, h_i * hd : (h_i + 1) * hd]  # (T2, hd)
            kh = kp_ref[0][:, kv_i * hd : (kv_i + 1) * hd]  # (page, hd)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            _fold(m_s, l_s, acc_s, h_i, hd, s, valid,
                  vp_ref[0][:, kv_i * hd : (kv_i + 1) * hd])

    @pl.when(j == maxp)
    def _in_batch():
        # draft rows: same-slot causal prefix, including self. Row order
        # inside a slot IS draft order, so the causal condition is col <= row.
        cid = jax.lax.broadcasted_iota(jnp.int32, (1, t2), 1)
        valid = row_b & ((cid // sq) == b) & (cid <= rid)  # (T2, T2)
        for h_i in range(h_total):
            kv_i = h_i // g
            qh = q_ref[:, h_i * hd : (h_i + 1) * hd]
            kh = kt_ref[:, kv_i * hd : (kv_i + 1) * hd]  # (T2, hd)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            _fold(m_s, l_s, acc_s, h_i, hd, s, valid,
                  vt_ref[:, kv_i * hd : (kv_i + 1) * hd])

    if commit:

        @pl.when(j >= maxp)
        def _commit_tail():
            # fused scatter epilogue: rewrite this step's tail page (streamed
            # in through kp_ref/vp_ref by the same index map that the output
            # flushes back through) with the draft rows that land in it.
            # Step maxp handles the page holding pos_b, step maxp+1 the page
            # holding pos_b + sq - 1 (the span may straddle a boundary; when
            # it does not, both steps rewrite the same page identically).
            pos_b = posp_ref[b]
            this_col = jnp.where(j == maxp, pos_b // page, (pos_b + sq - 1) // page)
            off_iota = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
            k_acc = kp_ref[0]
            v_acc = vp_ref[0]
            # slot b's draft rows: the dynamic slot index lands on the untiled
            # leading axis (a per-slot (sq, KV*hd) BlockSpec would break the
            # (8, 128) block rule for sq < 8, and a dynamic one-row slice of
            # the bf16 panel is not sublane-aligned)
            k_rows = kslot_ref[b]
            v_rows = vslot_ref[b]
            for i in range(sq):
                abs_i = pos_b + i
                pi = abs_i // page
                mapped = bt_ref[b, jnp.where(pi < maxp, pi, 0)] >= 0
                ok = (pi == this_col) & (pi < maxp) & mapped
                sel = (off_iota == (abs_i - pi * page)) & ok  # (page, 1)
                k_acc = jnp.where(sel, k_rows[i : i + 1, :], k_acc)
                v_acc = jnp.where(sel, v_rows[i : i + 1, :], v_acc)
            kp_o_ref[0] = k_acc
            vp_o_ref[0] = v_acc

    @pl.when((b == b_slots - 1) & (j == last_j))
    def _finalize():
        # l can never be 0 here (every row at least sees itself), but keep
        # the guarded divide for uniformity with the ragged kernel
        for h_i in range(h_total):
            l_h = jnp.maximum(l_s[:, h_i : h_i + 1], 1e-30)
            o_ref[:, h_i * hd : (h_i + 1) * hd] = (
                acc_s[:, h_i * hd : (h_i + 1) * hd] / l_h
            ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("commit", "interpret"))
def paged_decode_kernel(q, kp, vp, kt, vt, bt, pos, *, commit: bool = True,
                        interpret: bool = False):
    """Pallas launch wrapper; same signature/semantics as the ref.

    With ``commit=True`` returns ``(out, kp_new, vp_new)`` where the pools
    are aliased in place (the caller's kp/vp buffers are donated); with
    ``commit=False`` returns ``out`` only and never touches the pools —
    the scan-stacked model paths use this and batch ONE page commit per
    layer after the scan.
    """
    b, sq, h, hd = q.shape
    kv = kt.shape[2]
    g = h // kv
    maxp = bt.shape[1]
    page = kp.shape[1]
    validate_paged_decode(b, sq, h, kv, hd, maxp, page)
    t2 = b * sq

    q2 = q.reshape(t2, h * hd)
    kp2 = kp.reshape(kp.shape[0], page, kv * hd)
    vp2 = vp.reshape(vp.shape[0], page, kv * hd)
    kt2 = kt.reshape(t2, kv * hd)
    vt2 = vt.reshape(t2, kv * hd)
    pos32 = pos.astype(jnp.int32)
    pos_c = jnp.repeat(pos32, sq).reshape(t2, 1)
    bt32 = bt.astype(jnp.int32)
    # clamped tail PAGE ids for the epilogue streams (invalid -> page 0,
    # reads are harmless and writes are predicated off in-kernel)
    sl = jnp.arange(b)
    pi0 = jnp.clip(pos32 // page, 0, maxp - 1)
    pi1 = jnp.clip((pos32 + sq - 1) // page, 0, maxp - 1)
    tails = jnp.stack(
        [jnp.maximum(bt32[sl, pi0], 0), jnp.maximum(bt32[sl, pi1], 0)], axis=-1
    )

    kernel = functools.partial(
        _paged_decode_fwd,
        b_slots=b, sq=sq, maxp=maxp, page=page, g=g, hd=hd, h_total=h,
        scale=hd**-0.5, commit=commit,
    )

    n_j = maxp + 2 if commit else maxp + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_j),
        in_specs=[
            pl.BlockSpec((t2, h * hd), lambda bi, ji, bts, tls, pp: (0, 0)),
            # cache sweep reads bt[bi, ji] (unmapped -1 clamps to page 0,
            # masked in-kernel); the epilogue steps (ji >= maxp) stream the
            # pre-clamped tail pages so the commit can copy-modify-flush them
            pl.BlockSpec(
                (1, page, kv * hd),
                lambda bi, ji, bts, tls, pp: (
                    jnp.where(
                        ji < maxp,
                        jnp.where(
                            bts[bi, jnp.where(ji < maxp, ji, 0)] < 0,
                            0,
                            bts[bi, jnp.where(ji < maxp, ji, 0)],
                        ),
                        jnp.where(ji == maxp, tls[bi, 0], tls[bi, 1]),
                    ),
                    0,
                    0,
                ),
            ),
            pl.BlockSpec(
                (1, page, kv * hd),
                lambda bi, ji, bts, tls, pp: (
                    jnp.where(
                        ji < maxp,
                        jnp.where(
                            bts[bi, jnp.where(ji < maxp, ji, 0)] < 0,
                            0,
                            bts[bi, jnp.where(ji < maxp, ji, 0)],
                        ),
                        jnp.where(ji == maxp, tls[bi, 0], tls[bi, 1]),
                    ),
                    0,
                    0,
                ),
            ),
            pl.BlockSpec((t2, kv * hd), lambda bi, ji, bts, tls, pp: (0, 0)),
            pl.BlockSpec((t2, kv * hd), lambda bi, ji, bts, tls, pp: (0, 0)),
            pl.BlockSpec((b, sq, kv * hd), lambda bi, ji, bts, tls, pp: (0, 0, 0)),
            pl.BlockSpec((b, sq, kv * hd), lambda bi, ji, bts, tls, pp: (0, 0, 0)),
            pl.BlockSpec((t2, 1), lambda bi, ji, bts, tls, pp: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t2, h * hd), lambda bi, ji, bts, tls, pp: (0, 0)),
        ] + ([
            pl.BlockSpec(
                (1, page, kv * hd),
                lambda bi, ji, bts, tls, pp: (
                    jnp.where(ji <= maxp, tls[bi, 0], tls[bi, 1]),
                    0,
                    0,
                ),
            ),
            pl.BlockSpec(
                (1, page, kv * hd),
                lambda bi, ji, bts, tls, pp: (
                    jnp.where(ji <= maxp, tls[bi, 0], tls[bi, 1]),
                    0,
                    0,
                ),
            ),
        ] if commit else []),
        scratch_shapes=[
            pltpu.VMEM((t2, h), jnp.float32),
            pltpu.VMEM((t2, h), jnp.float32),
            pltpu.VMEM((t2, h * hd), jnp.float32),
        ],
    )
    out_shape = [jax.ShapeDtypeStruct((t2, h * hd), vt.dtype)]
    if commit:
        out_shape += [
            jax.ShapeDtypeStruct(kp2.shape, kp2.dtype),
            jax.ShapeDtypeStruct(vp2.shape, vp2.dtype),
        ]
    if not commit:
        # trim the unused operands' bodies via a thin adapter: the body
        # signature keeps the full operand list, outputs simply lack the
        # tail write-backs
        def kernel_nc(bt_r, tl_r, pp_r, q_r, kp_r, vp_r, kt_r, vt_r, ks_r,
                      vs_r, pc_r, o_r, m_r, l_r, a_r):
            return kernel(bt_r, tl_r, pp_r, q_r, kp_r, vp_r, kt_r, vt_r,
                          ks_r, vs_r, pc_r, o_r, None, None, m_r, l_r, a_r)

        body = kernel_nc
    else:
        body = kernel
    res = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operand order: bt, tails, posp, q2, kp2, vp2, kt2, vt2, kslot,
        # vslot, pos_c -> kp2/vp2 are operands 4/5, aliased onto outputs 1/2
        input_output_aliases={4: 1, 5: 2} if commit else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY)
        ),
        interpret=interpret,
        name="paged_decode",
    )(bt32, tails, pos32, q2, kp2, vp2, kt2, vt2,
      kt.reshape(b, sq, kv * hd), vt.reshape(b, sq, kv * hd), pos_c)
    if commit:
        out, kp_new, vp_new = res
        return (
            out.reshape(b, sq, h, hd),
            kp_new.reshape(kp.shape),
            vp_new.reshape(vp.shape),
        )
    return res[0].reshape(b, sq, h, hd) if isinstance(res, (list, tuple)) else res.reshape(b, sq, h, hd)
