"""Decode-shaped fused dual-component GEMM — the M=B<=8 regime of §4.3.

The prefill kernel (twinquant_dual_gemm.py) is scheduled for M>=128 panels:
it sweeps N blocks while re-reading the quantized activation panel from a
VMEM scratch and pays a (M/bm, N/bn, K/bk) grid's worth of index arithmetic.
In the serving engine's decode steps M is the slot count (1..8), so that
schedule wastes almost the entire MXU tile on padding and re-walks K once
per N block for the low-rank path bookkeeping.

This kernel is the decode-matched schedule:

* the whole activation panel ``X (m<=8, K)`` is **resident in VMEM** for the
  kernel's lifetime (constant-index BlockSpec) — quantized exactly once, at
  the first grid step, into int8 scratch; no N-sweep requantization logic;
* **both low-rank factors are pinned whole in VMEM** (``U``: K*r/2 bytes,
  ``V``: r*N/2 bytes — a few hundred KB at LLaMA3-8B shapes), so the
  low-rank intermediate ``H = requant(dq(Xq @ Uq))`` is computed and
  requantized once, at the first grid step, and every N block only pays the
  tiny (m, r) x (r, bn) second GEMM in its epilogue;
* each scale group's activation scale lives in its own ``(m, 1)`` slab of a
  group-major scratch, so the fori_loop's dynamic group index lands on the
  untiled leading axis: no store is narrower than a lane tile at a dynamic
  lane offset (Mosaic refuses those);
* the grid is **one-dimensional over N** (``(N/bn,)``): each step streams a
  whole-K ``(K/2, bn)`` packed residual tile — the only HBM traffic that
  scales with N — computes the residual component with a fori_loop over
  scale groups (bounded unroll), adds the low-rank epilogue, and writes the
  (m, bn) output tile once.

Numerics are identical to kernels/ref.dual_gemm_ref: same group structure,
same rounding, same ascending-group f32 accumulation order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import qmax_for_bits
from repro.kernels.autotune import DECODE_M_MAX
from repro.kernels.contracts import validate_dual_gemv, validate_dual_gemv_group
from repro.kernels.ref import TwinQuantGroupWeights, TwinQuantWeights


__all__ = ["dual_gemv", "dual_gemv_group", "DECODE_M_MAX"]


def _unpack_rows(p: jax.Array) -> jax.Array:
    """(G/2, w) packed int8 -> (G, w) int8 (group-split layout)."""
    p32 = p.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(p32, 24), 28)
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)


def _int8_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


def _dual_gemv_kernel(
    # inputs
    x_ref,  # (m, K)     bf16 — whole panel, resident
    up_ref,  # (K/2, r)  int8 packed — whole, resident
    us_ref,  # (K/G, r)  f32
    vp_ref,  # (r/2, N)  int8 packed — whole, resident
    vs_ref,  # (r/gr, N) f32
    rp_ref,  # (K/2, bn) int8 packed — streamed per N block
    rs_ref,  # (K/G, bn) f32
    # output
    o_ref,  # (m, bn)    bf16
    # scratch (persist across the sequential N grid)
    xq_s,  # (m, K)      int8 — quantized activation panel
    xs_s,  # (K/G, m, 1) f32  — its per-group scales, group-major
    hq_s,  # (m, r)      int8 — requantized low-rank intermediate
    hs_s,  # (m, r/gr)   f32  — its scales
    *,
    bn: int,
    G: int,
    gr: int,
    r: int,
    a_bits: int,
    n_groups: int,
):
    ni = pl.program_id(0)
    a_qmax = qmax_for_bits(a_bits)
    m = xq_s.shape[0]

    # ---- first grid step only: quantize the whole X panel and build H.
    # No per-N-block requantization state machine — X and U are resident, so
    # one ascending fori_loop over scale groups does the entire low-rank
    # front half of the dual GEMM.
    @pl.when(ni == 0)
    def _quantize_panel_and_h():
        def body(g, h):
            xg = x_ref[:, pl.ds(g * G, G)].astype(jnp.float32)  # (m, G)
            amax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)  # (m, 1)
            scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
            q = jnp.clip(jnp.round(xg / scale), -a_qmax, a_qmax).astype(jnp.int8)
            xq_s[:, pl.ds(g * G, G)] = q
            xs_s[g] = scale
            ug = _unpack_rows(up_ref[pl.ds(g * (G // 2), G // 2), :])  # (G, r)
            us = us_ref[pl.ds(g, 1), :]  # (1, r)
            return h + _int8_dot(q, ug).astype(jnp.float32) * scale * us

        h = jax.lax.fori_loop(0, n_groups, body, jnp.zeros((m, r), jnp.float32))
        for gg in range(r // gr):  # requantize H at a_bits (r/gr is 1-2)
            hg = h[:, gg * gr : (gg + 1) * gr]
            amax = jnp.max(jnp.abs(hg), axis=1, keepdims=True)
            scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
            hq_s[:, gg * gr : (gg + 1) * gr] = jnp.clip(
                jnp.round(hg / scale), -a_qmax, a_qmax
            ).astype(jnp.int8)
            hs_s[:, gg : gg + 1] = scale

    # ---- every grid step: whole-K residual component for this N block
    def resid(g, acc):
        xg = xq_s[:, pl.ds(g * G, G)]  # (m, G) int8
        sg = xs_s[g]  # (m, 1)
        rg = _unpack_rows(rp_ref[pl.ds(g * (G // 2), G // 2), :])  # (G, bn)
        rs = rs_ref[pl.ds(g, 1), :]  # (1, bn)
        return acc + _int8_dot(xg, rg).astype(jnp.float32) * sg * rs

    out = jax.lax.fori_loop(0, n_groups, resid, jnp.zeros((m, bn), jnp.float32))

    # ---- epilogue: second low-rank GEMM from the resident V + one write-back
    for gg in range(r // gr):
        hqg = hq_s[:, gg * gr : (gg + 1) * gr]  # (m, gr)
        vg = _unpack_rows(vp_ref[gg * (gr // 2) : (gg + 1) * (gr // 2), pl.ds(ni * bn, bn)])
        pv = _int8_dot(hqg, vg).astype(jnp.float32)
        out = out + pv * hs_s[:, gg : gg + 1] * vs_ref[gg : gg + 1, pl.ds(ni * bn, bn)]
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dual_gemv(
    x: jax.Array,
    w: TwinQuantWeights,
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Decode-shaped fused dual-component matmul. x: (M<=8, K) -> (M, N) bf16.

    N must be a multiple of ``block_n`` and K a multiple of ``w.group``; the
    dispatch layer routes anything else to the jnp oracle.
    """
    m, k = x.shape
    n = w.ndim_out
    r = w.rank
    G, gr = w.group, w.rgroup
    # divisibility + resident-panel VMEM contracts (raise ContractError with
    # the violated relation before Mosaic sees the launch)
    validate_dual_gemv(m, n, k, r, G, gr, block_n, decode_m_max=DECODE_M_MAX)

    kernel = functools.partial(
        _dual_gemv_kernel,
        bn=block_n, G=G, gr=gr, r=r, a_bits=w.a_bits, n_groups=k // G,
    )

    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            # resident operands: constant index maps, fetched exactly once
            pl.BlockSpec((m, k), lambda ni: (0, 0)),
            pl.BlockSpec((k // 2, r), lambda ni: (0, 0)),
            pl.BlockSpec((k // G, r), lambda ni: (0, 0)),
            pl.BlockSpec((r // 2, n), lambda ni: (0, 0)),
            pl.BlockSpec((r // gr, n), lambda ni: (0, 0)),
            # streamed residual tile: whole K, one N block per grid step
            pl.BlockSpec((k // 2, block_n), lambda ni: (0, ni)),
            pl.BlockSpec((k // G, block_n), lambda ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda ni: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((m, k), jnp.int8),
            pltpu.VMEM((k // G, m, 1), jnp.float32),
            pltpu.VMEM((m, r), jnp.int8),
            pltpu.VMEM((m, r // gr), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # sequential N sweep: scratch (Xq, H) persists across grid steps
            dimension_semantics=(pltpu.ARBITRARY,),
        ),
        interpret=interpret,
        name="twinquant_dual_gemv",
    )(x, w.up, w.us, w.vp, w.vs, w.rp, w.rs)


# ---------------------------------------------------------------------------
# fused projection group (q/k/v, gate/up): one launch for all sibling outputs
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dual_gemv_group(
    x: jax.Array,
    gw: TwinQuantGroupWeights,
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Decode-shaped fused dual GEMM over a sibling-projection group.

    x: (M<=8, K) -> (M, sum N_j) bf16. One launch computes every segment of
    the group: the X panel is quantized ONCE (instead of once per sibling),
    H = requant(dq(Xq @ [U_0|U_1|...])) is built once over the stacked rank
    axis, and the 1-D grid streams the concatenated residual. Each N block
    belongs to exactly one segment (``block_n`` divides every ``N_j``); its
    epilogue consumes only that segment's H columns against that segment's
    resident V — the block-diagonal-V contraction without materialized
    zeros — so every output segment is bit-exact vs the unfused kernel.

    ``block_n`` must divide every segment's N and K must be a multiple of
    ``gw.group``; the dispatch layer routes anything else to the oracle.
    """
    m, k = x.shape
    G = gw.group
    seg_n, seg_r, grs = gw.seg_n, gw.seg_r, gw.rgroups
    n_segs = len(seg_n)
    r_total = gw.rank
    # divisibility + resident-panel VMEM contracts (per-segment checks
    # included: block_n must never straddle a segment boundary)
    validate_dual_gemv_group(m, k, G, seg_n, seg_r, grs, block_n, decode_m_max=DECODE_M_MAX)
    n_groups = k // G
    bn = block_n
    # static segment tables: N-block ownership, rank offsets, H-scale offsets
    nblk_off = tuple(no // bn for no in gw.n_offsets)
    nblk_end = tuple((no + nj) // bn for no, nj in zip(gw.n_offsets, seg_n))
    r_off = gw.r_offsets
    hs_off, hs_cols = [], 0
    for rj, gr in zip(seg_r, grs):
        hs_off.append(hs_cols)
        hs_cols += rj // gr
    hs_off = tuple(hs_off)
    a_bits = gw.a_bits

    def kernel(*args):
        x_ref, up_ref, us_ref = args[:3]
        vrefs = args[3 : 3 + 2 * n_segs]
        rp_ref, rs_ref, o_ref = args[3 + 2 * n_segs : 6 + 2 * n_segs]
        xq_s, xs_s, hq_s, hs_s = args[6 + 2 * n_segs :]
        ni = pl.program_id(0)
        a_qmax = qmax_for_bits(a_bits)

        # ---- first grid step: quantize the X panel once, build the stacked
        # H = dq(Xq @ [U_0|U_1|...]), requantize each segment's H columns with
        # that segment's OWN rank-group structure (static offsets/sizes)
        @pl.when(ni == 0)
        def _quantize_panel_and_h():
            def body(g, h):
                xg = x_ref[:, pl.ds(g * G, G)].astype(jnp.float32)  # (m, G)
                amax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)
                scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
                q = jnp.clip(jnp.round(xg / scale), -a_qmax, a_qmax).astype(jnp.int8)
                xq_s[:, pl.ds(g * G, G)] = q
                xs_s[g] = scale
                ug = _unpack_rows(up_ref[pl.ds(g * (G // 2), G // 2), :])
                us = us_ref[pl.ds(g, 1), :]
                return h + _int8_dot(q, ug).astype(jnp.float32) * scale * us

            h = jax.lax.fori_loop(0, n_groups, body, jnp.zeros((m, r_total), jnp.float32))
            for j in range(n_segs):
                gr = grs[j]
                for gg in range(seg_r[j] // gr):
                    base = r_off[j] + gg * gr
                    hg = h[:, base : base + gr]
                    amax = jnp.max(jnp.abs(hg), axis=1, keepdims=True)
                    scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
                    hq_s[:, base : base + gr] = jnp.clip(
                        jnp.round(hg / scale), -a_qmax, a_qmax
                    ).astype(jnp.int8)
                    hs_s[:, hs_off[j] + gg : hs_off[j] + gg + 1] = scale

        # ---- every grid step: whole-K residual for this (concatenated) N block
        def resid(g, acc):
            xg = xq_s[:, pl.ds(g * G, G)]
            sg = xs_s[g]
            rg = _unpack_rows(rp_ref[pl.ds(g * (G // 2), G // 2), :])
            rs = rs_ref[pl.ds(g, 1), :]
            return acc + _int8_dot(xg, rg).astype(jnp.float32) * sg * rs

        out = jax.lax.fori_loop(0, n_groups, resid, jnp.zeros((m, bn), jnp.float32))

        # ---- epilogue: exactly one segment owns this N block; add its
        # low-rank contribution from its own H columns + resident V segment
        for j in range(n_segs):

            @pl.when((ni >= nblk_off[j]) & (ni < nblk_end[j]))
            def _seg_epilogue(j=j):
                vp_ref, vs_ref = vrefs[2 * j], vrefs[2 * j + 1]
                loc = (ni - nblk_off[j]) * bn  # column offset inside segment j
                gr = grs[j]
                acc = out
                for gg in range(seg_r[j] // gr):
                    hqg = hq_s[:, r_off[j] + gg * gr : r_off[j] + (gg + 1) * gr]
                    vg = _unpack_rows(
                        vp_ref[gg * (gr // 2) : (gg + 1) * (gr // 2), pl.ds(loc, bn)]
                    )
                    pv = _int8_dot(hqg, vg).astype(jnp.float32)
                    acc = acc + (
                        pv
                        * hs_s[:, hs_off[j] + gg : hs_off[j] + gg + 1]
                        * vs_ref[gg : gg + 1, pl.ds(loc, bn)]
                    )
                o_ref[...] = acc.astype(o_ref.dtype)

    n_total = gw.ndim_out
    in_specs = [
        # resident operands: constant index maps, fetched exactly once
        pl.BlockSpec((m, k), lambda ni: (0, 0)),
        pl.BlockSpec((k // 2, r_total), lambda ni: (0, 0)),
        pl.BlockSpec((k // G, r_total), lambda ni: (0, 0)),
    ]
    for vp, vs in zip(gw.vps, gw.vss):
        in_specs.append(pl.BlockSpec(vp.shape, lambda ni: (0, 0)))
        in_specs.append(pl.BlockSpec(vs.shape, lambda ni: (0, 0)))
    in_specs += [
        # streamed concatenated residual tile: whole K, one N block per step
        pl.BlockSpec((k // 2, bn), lambda ni: (0, ni)),
        pl.BlockSpec((k // G, bn), lambda ni: (0, ni)),
    ]
    operands = [x, gw.up, gw.us]
    for vp, vs in zip(gw.vps, gw.vss):
        operands += [vp, vs]
    operands += [gw.rp, gw.rs]

    return pl.pallas_call(
        kernel,
        grid=(n_total // bn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bn), lambda ni: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n_total), jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((m, k), jnp.int8),
            pltpu.VMEM((k // G, m, 1), jnp.float32),
            pltpu.VMEM((m, r_total), jnp.int8),
            pltpu.VMEM((m, hs_cols), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # sequential N sweep: scratch (Xq, H) persists across grid steps
            dimension_semantics=(pltpu.ARBITRARY,),
        ),
        interpret=interpret,
        name="twinquant_dual_gemv_group",
    )(*operands)
