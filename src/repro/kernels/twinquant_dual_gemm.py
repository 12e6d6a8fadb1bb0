"""Fused dual-component W4A4/W4A8 GEMM — the paper's §4.3 kernel, TPU-native.

One ``pl.pallas_call`` computes

    Y = dq(Xq @ Rq)  +  dq( requant(dq(Xq @ Uq)) @ Vq )

for a TwinQuant-decomposed linear layer, with:

* activations quantized **in-kernel, once per M×K tile** (at the first N
  block) into a VMEM scratch and reused for both components and all N blocks
  — the paper's "quantize the input activation tile once";
* the two-stage low-rank path pipelined **entirely in VMEM**: the f32
  intermediate ``H = dq(Xq @ Uq)`` lives in a scratch accumulator across K
  steps, is re-quantized on the fly at the last K step of the first N block
  (scale ``s_H`` estimated from the accumulator, as in the paper), and is
  consumed by the second int GEMM without ever touching HBM;
* both component outputs merged in a **single epilogue** with one bf16
  write-back per output tile.

Layout rules the TPU compiler enforces (interpret mode does not):

* the K block's activation scales are assembled into one ``(bm, bk/G)``
  value and stored whole into a K-block-major scratch ``(K/bk, bm, bk/G)``:
  the grid's dynamic K index lands on the untiled leading axis, so no store
  is narrower than a lane tile at a dynamic lane offset;
* the residual scales ``rs`` arrive as a whole-K ``(K/G, bn)`` strip per N
  block (the full K/G extent satisfies the (8, 128) block rule for any
  ``block_k``) and the kernel slices its K block's rows out of it.

Grid is ``(M/bm, N/bn, K/bk)`` with K innermost
(``dimension_semantics = (parallel, arbitrary, arbitrary)``). HBM traffic:

* weights (U, V, R) move at 4 bits/value (group-split nibble packing — see
  kernels/ref.py for the layout invariant that keeps packed tiles local to
  their scale group);
* U is small (K×r/2 bytes) and is pinned whole in VMEM via a constant-index
  BlockSpec, so it is fetched exactly once per kernel invocation;
* X is fetched once per M block: its index map degenerates to block (m, 0)
  for n > 0, and Pallas skips refetches when the block index is unchanged.

The MXU consumes int8 (TPU has no int4 MMA — see DESIGN.md §3): packed
nibbles are sign-extended to int8 in VMEM by the VPU, and all dots accumulate
in int32 via ``preferred_element_type``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import qmax_for_bits
from repro.kernels.contracts import validate_dual_gemm, validate_dual_gemm_group
from repro.kernels.ref import TwinQuantGroupWeights, TwinQuantWeights

__all__ = ["dual_gemm", "dual_gemm_group", "DEFAULT_BLOCKS"]

DEFAULT_BLOCKS = dict(block_m=128, block_n=256, block_k=512)


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


def _dual_gemm_kernel(
    # inputs
    x_ref,  # (bm, bk)   bf16 — block (m, k) when n==0 else (m, 0)
    up_ref,  # (K/2, r)  int8 packed — whole array, fetched once
    us_ref,  # (K/G, r)  f32
    vp_ref,  # (r/2, bn) int8 packed
    vs_ref,  # (r/gr, bn) f32
    rp_ref,  # (bk/2, bn) int8 packed
    rs_ref,  # (K/G, bn) f32 — whole-K strip, fetched once per N block
    # output
    o_ref,  # (bm, bn)  bf16
    # scratch
    xq_s,  # (bm, K)    int8 — quantized activation row-panel
    xs_s,  # (K/bk, bm, bk/G) f32 — its per-group scales, K-block-major
    h_s,  # (bm, r)     f32  — low-rank intermediate accumulator
    hq_s,  # (bm, r)    int8 — requantized H
    hs_s,  # (bm, r/gr) f32  — H scales
    acc_s,  # (bm, bn)  f32  — residual-component accumulator
    *,
    bk: int,
    G: int,
    gr: int,
    r: int,
    a_bits: int,
    n_k: int,
):
    n = pl.program_id(1)
    k = pl.program_id(2)
    a_qmax = qmax_for_bits(a_bits)
    gpb = bk // G  # scale groups per K block

    @pl.when(k == 0)
    def _zero_acc():
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((n == 0) & (k == 0))
    def _zero_h():
        h_s[...] = jnp.zeros_like(h_s)

    # ---- stage A (first N block only): quantize the X tile into scratch and
    # accumulate the first low-rank GEMM H += dq(Xq_g @ Uq_g)
    @pl.when(n == 0)
    def _quantize_and_lowrank():
        x = x_ref[...].astype(jnp.float32)  # (bm, bk)
        lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], gpb), 1)
        scales = jnp.zeros((x.shape[0], gpb), jnp.float32)
        for g in range(gpb):
            xg = x[:, g * G : (g + 1) * G]
            amax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)  # (bm, 1)
            scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
            q = jnp.clip(jnp.round(xg / scale), -a_qmax, a_qmax).astype(jnp.int8)
            xq_s[:, pl.ds(k * bk + g * G, G)] = q
            scales = jnp.where(lane == g, scale, scales)
            # first low-rank GEMM on the freshly quantized group
            ug = _unpack_rows(up_ref[pl.ds(k * (bk // 2) + g * (G // 2), G // 2), :])  # (G, r)
            us = us_ref[pl.ds(k * gpb + g, 1), :]  # (1, r)
            ph = _int8_dot(q, ug).astype(jnp.float32)
            h_s[...] += ph * scale * us
        xs_s[k] = scales

    # ---- stage B: residual-component partial for this (n, k) tile
    sk = xs_s[k]  # (bm, gpb)
    for g in range(gpb):
        xg = xq_s[:, pl.ds(k * bk + g * G, G)]  # (bm, G) int8
        sg = sk[:, g : g + 1]  # (bm, 1)
        rg = _unpack_rows(rp_ref[g * (G // 2) : (g + 1) * (G // 2), :])  # (G, bn)
        rs = rs_ref[pl.ds(k * gpb + g, 1), :]  # (1, bn)
        pr = _int8_dot(xg, rg).astype(jnp.float32)
        acc_s[...] += pr * sg * rs

    # ---- stage C (first N block, last K step): requantize H on the fly
    @pl.when((n == 0) & (k == n_k - 1))
    def _requantize_h():
        h = h_s[...]
        for gg in range(r // gr):
            hg = h[:, gg * gr : (gg + 1) * gr]
            amax = jnp.max(jnp.abs(hg), axis=1, keepdims=True)
            scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
            hq_s[:, gg * gr : (gg + 1) * gr] = jnp.clip(
                jnp.round(hg / scale), -a_qmax, a_qmax
            ).astype(jnp.int8)
            hs_s[:, gg : gg + 1] = scale

    # ---- stage D (last K step): single epilogue — second low-rank GEMM +
    # merge with the residual accumulator + one write-back
    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_s[...]
        for gg in range(r // gr):
            hqg = hq_s[:, gg * gr : (gg + 1) * gr]  # (bm, gr)
            vg = _unpack_rows(vp_ref[gg * (gr // 2) : (gg + 1) * (gr // 2), :])
            pv = _int8_dot(hqg, vg).astype(jnp.float32)
            out = out + pv * hs_s[:, gg : gg + 1] * vs_ref[gg : gg + 1, :]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def dual_gemm(
    x: jax.Array,
    w: TwinQuantWeights,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused dual-component quantized matmul. x: (M, K) -> (M, N) bf16.

    M, N, K must be multiples of the block sizes (the ops.py wrapper pads).
    """
    m, k = x.shape
    n = w.ndim_out
    r = w.rank
    G, gr = w.group, w.rgroup
    # grid-coverage/divisibility + VMEM-budget contracts (raise ContractError
    # with the violated relation before Mosaic sees the launch)
    validate_dual_gemm(m, n, k, r, G, gr, block_m, block_n, block_k)
    n_k = k // block_k

    grid = (m // block_m, n // block_n, n_k)

    kernel = functools.partial(
        _dual_gemm_kernel,
        bk=block_k, G=G, gr=gr, r=r, a_bits=w.a_bits, n_k=n_k,
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # X: fetched only during the n==0 sweep (index pins to (m, 0) after)
            pl.BlockSpec(
                (block_m, block_k),
                lambda mi, ni, ki: (mi, jnp.where(ni == 0, ki, 0)),
            ),
            # U pinned whole in VMEM (K*r/2 bytes), fetched once
            pl.BlockSpec((k // 2, r), lambda mi, ni, ki: (0, 0)),
            pl.BlockSpec((k // G, r), lambda mi, ni, ki: (0, 0)),
            pl.BlockSpec((r // 2, block_n), lambda mi, ni, ki: (0, ni)),
            pl.BlockSpec((r // gr, block_n), lambda mi, ni, ki: (0, ni)),
            pl.BlockSpec((block_k // 2, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((k // G, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((block_m, k), jnp.int8),
            pltpu.VMEM((n_k, block_m, block_k // G), jnp.float32),
            pltpu.VMEM((block_m, r), jnp.float32),
            pltpu.VMEM((block_m, r), jnp.int8),
            pltpu.VMEM((block_m, r // gr), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY, pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="twinquant_dual_gemm",
    )(x, w.up, w.us, w.vp, w.vs, w.rp, w.rs)


# ---------------------------------------------------------------------------
# fused projection group (q/k/v, gate/up): one launch for all sibling outputs
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def dual_gemm_group(
    x: jax.Array,
    gw: TwinQuantGroupWeights,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Prefill-shaped fused dual GEMM over a sibling-projection group.

    x: (M, K) -> (M, sum N_j) bf16, M/K multiples of the blocks and
    ``block_n`` dividing every segment's N (so each N block is owned by one
    segment). Relative to running the unfused kernel once per sibling, the
    X tile is quantized ONCE (at the n==0 sweep) instead of once per
    sibling, the X panel is fetched from HBM once instead of S times, and
    the stacked-rank H accumulator is built in a single pass over K. Each
    output block's epilogue contracts only the owning segment's H columns
    against that segment's V (block-diagonal V without materialized zeros),
    and H requantization uses each segment's own rank-group structure — so
    every output segment is bit-exact vs the unfused kernel at the same
    blocks.
    """
    m, k = x.shape
    G = gw.group
    seg_n, seg_r, grs = gw.seg_n, gw.seg_r, gw.rgroups
    n_segs = len(seg_n)
    r_total = gw.rank
    n_total = gw.ndim_out
    # grid-coverage/divisibility + VMEM-budget contracts (per-segment checks
    # included: block_n must never straddle a segment boundary)
    validate_dual_gemm_group(m, k, G, seg_n, seg_r, grs, block_m, block_n, block_k)
    n_k = k // block_k
    bm, bn, bk = block_m, block_n, block_k
    gpb = bk // G  # scale groups per K block
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
        xq_s, xs_s, h_s, hq_s, hs_s, acc_s = args[6 + 2 * n_segs :]
        ni = pl.program_id(1)
        ki = pl.program_id(2)
        a_qmax = qmax_for_bits(a_bits)

        @pl.when(ki == 0)
        def _zero_acc():
            acc_s[...] = jnp.zeros_like(acc_s)

        @pl.when((ni == 0) & (ki == 0))
        def _zero_h():
            h_s[...] = jnp.zeros_like(h_s)

        # ---- stage A (first N block only): quantize the X tile once into
        # scratch and accumulate the stacked low-rank GEMM H += dq(Xq @ Uq)
        @pl.when(ni == 0)
        def _quantize_and_lowrank():
            xv = x_ref[...].astype(jnp.float32)  # (bm, bk)
            lane = jax.lax.broadcasted_iota(jnp.int32, (bm, gpb), 1)
            scales = jnp.zeros((bm, gpb), jnp.float32)
            for g in range(gpb):
                xg = xv[:, g * G : (g + 1) * G]
                amax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)
                scale = jnp.where(amax > 0, amax / a_qmax, 1.0)
                q = jnp.clip(jnp.round(xg / scale), -a_qmax, a_qmax).astype(jnp.int8)
                xq_s[:, pl.ds(ki * bk + g * G, G)] = q
                scales = jnp.where(lane == g, scale, scales)
                ug = _unpack_rows(up_ref[pl.ds(ki * (bk // 2) + g * (G // 2), G // 2), :])
                us = us_ref[pl.ds(ki * gpb + g, 1), :]
                ph = _int8_dot(q, ug).astype(jnp.float32)
                h_s[...] += ph * scale * us
            xs_s[ki] = scales

        # ---- stage B: residual partial for this (concatenated-N, K) tile
        sk = xs_s[ki]  # (bm, gpb)
        for g in range(gpb):
            xg = xq_s[:, pl.ds(ki * bk + g * G, G)]
            sg = sk[:, g : g + 1]
            rg = _unpack_rows(rp_ref[g * (G // 2) : (g + 1) * (G // 2), :])
            rs = rs_ref[pl.ds(ki * gpb + g, 1), :]
            pr = _int8_dot(xg, rg).astype(jnp.float32)
            acc_s[...] += pr * sg * rs

        # ---- stage C (first N block, last K step): requantize each
        # segment's H columns with that segment's OWN rank groups
        @pl.when((ni == 0) & (ki == n_k - 1))
        def _requantize_h():
            h = h_s[...]
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

        # ---- stage D (last K step): the owning segment's second low-rank
        # GEMM + merge with the residual accumulator + one write-back
        for j in range(n_segs):

            @pl.when((ki == n_k - 1) & (ni >= nblk_off[j]) & (ni < nblk_end[j]))
            def _seg_epilogue(j=j):
                vp_ref, vs_ref = vrefs[2 * j], vrefs[2 * j + 1]
                loc = (ni - nblk_off[j]) * bn  # column offset inside segment j
                gr = grs[j]
                acc = acc_s[...]
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

    in_specs = [
        # X: fetched only during the n==0 sweep (index pins to (m, 0) after)
        pl.BlockSpec(
            (bm, bk),
            lambda mi, ni, ki: (mi, jnp.where(ni == 0, ki, 0)),
        ),
        # stacked U pinned whole in VMEM, fetched once
        pl.BlockSpec((k // 2, r_total), lambda mi, ni, ki: (0, 0)),
        pl.BlockSpec((k // G, r_total), lambda mi, ni, ki: (0, 0)),
    ]
    for vp, vs in zip(gw.vps, gw.vss):
        # per-segment V resident whole (rank is small; sliced per N block)
        in_specs.append(pl.BlockSpec(vp.shape, lambda mi, ni, ki: (0, 0)))
        in_specs.append(pl.BlockSpec(vs.shape, lambda mi, ni, ki: (0, 0)))
    in_specs += [
        pl.BlockSpec((bk // 2, bn), lambda mi, ni, ki: (ki, ni)),
        pl.BlockSpec((k // G, bn), lambda mi, ni, ki: (0, ni)),
    ]
    operands = [x, gw.up, gw.us]
    for vp, vs in zip(gw.vps, gw.vss):
        operands += [vp, vs]
    operands += [gw.rp, gw.rs]

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n_total // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n_total), jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((bm, k), jnp.int8),
            pltpu.VMEM((n_k, bm, gpb), jnp.float32),
            pltpu.VMEM((bm, r_total), jnp.float32),
            pltpu.VMEM((bm, r_total), jnp.int8),
            pltpu.VMEM((bm, hs_cols), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY, pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="twinquant_dual_gemm_group",
    )(*operands)
