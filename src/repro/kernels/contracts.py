"""Trace-time launch contracts for the quantized Pallas kernels.

Every fused-kernel launch in this repo depends on a web of structural
invariants — grid coverage (``m % block_m == 0``), BlockSpec divisibility
(``block_k % group``, ``rank % rgroup``), the group-split packing parity
(``group % 2``), and a VMEM footprint small enough for the resident-panel
schedules to actually pin their operands. Violating one used to surface as a
bare ``assert`` tuple, an opaque Mosaic lowering error, or (through the
dispatch layer) a silent ref-path fallback.

This module is the machine-checked version of those invariants:

* :func:`validate_dual_gemm` / :func:`validate_dual_gemv` /
  :func:`validate_dual_gemm_group` / :func:`validate_dual_gemv_group` /
  :func:`validate_w4a16` — grid-coverage + divisibility contracts shared by
  the kernel wrappers. They raise :class:`ContractError` (a ``ValueError``)
  with the violated relation, the offending values, and a hint — BEFORE
  ``pl.pallas_call`` hands the launch to Mosaic.
* :func:`check_blocks` / :func:`lane_aligned` — the TPU layout rules that
  interpret mode does not enforce: every BlockSpec block's last two dims are
  multiples of (8, 128) or equal the array's (the (8, 128) block rule), and
  a value stored or loaded at a dynamic lane offset spans whole 128-lane
  tiles (the lane-aligned store rule). The validators apply both, so a
  launch the chip's compiler would refuse raises here on every backend.
* :func:`vmem_footprint` / :func:`check_vmem` — a per-launch VMEM estimate
  computed from the kernel's BlockSpec block shapes and scratch shapes
  (streamed operands double-buffered, pinned/constant-index operands counted
  once), rejected with a per-buffer breakdown when it exceeds the budget.
* :func:`check_twinquant_pack` / :func:`check_twinquant_group_pack` /
  :func:`check_w4a16_pack` — shape/dtype consistency contracts on the packed
  weight containers, run at every ``kernels/dispatch.py`` entry so a
  malformed pack (field shapes that disagree with each other or with the
  activation) produces a diagnostic instead of garbage numerics or an
  indistinguishable ref fallback. Odd-but-internally-consistent shapes (N
  not 128-aligned, K not a group multiple) remain ROUTING decisions and are
  untouched here.

The checks run at trace time (all inputs are static shapes/ints), so under
``jax.jit`` they cost nothing on the execution path. The static analyzer
(``python -m repro.analysis``) accepts a ``validate_*`` call as the
divisibility guard for a wrapper's BlockSpec integer divisions.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax.numpy as jnp

__all__ = [
    "ContractError",
    "VMEM_BUDGET_BYTES",
    "check_paged_decode_args",
    "check_ragged_args",
    "check_twinquant_group_pack",
    "check_blocks",
    "check_twinquant_pack",
    "check_vmem",
    "check_w4a16_pack",
    "divisible",
    "lane_aligned",
    "validate_dual_gemm",
    "validate_dual_gemm_group",
    "validate_dual_gemv",
    "validate_dual_gemv_group",
    "validate_paged_decode",
    "validate_ragged_attention",
    "validate_w4a16",
    "vmem_footprint",
]


class ContractError(ValueError):
    """A kernel-launch or weight-pack contract violation, caught at trace
    time with a readable message — never a Mosaic error or a silent
    fallback."""


def _budget_bytes() -> int:
    """Per-core VMEM budget (bytes). ~16 MiB on current TPU generations;
    override with ``REPRO_VMEM_BUDGET_BYTES`` for other parts or for forcing
    the contract in tests."""
    return int(os.environ.get("REPRO_VMEM_BUDGET_BYTES", 16 * 2**20))


# module-level snapshot for introspection; check_vmem re-reads the env so
# tests can tighten the budget without reloading the module
VMEM_BUDGET_BYTES = _budget_bytes()


def divisible(a: int, b: int, what: str, *, kind: str, hint: str = "") -> None:
    """Contract: ``a % b == 0``. The shared primitive behind every BlockSpec
    integer division (``k // 2``, ``block_k // G``, ``r // gr``, ...)."""
    if b <= 0:
        raise ContractError(
            f"[{kind}] {what}: divisor must be positive, got {b}"
            + (f"\n  hint: {hint}" if hint else "")
        )
    if a % b != 0:
        raise ContractError(
            f"[{kind}] {what}: {a} is not a multiple of {b} "
            f"(remainder {a % b})" + (f"\n  hint: {hint}" if hint else "")
        )


def positive(value: int, what: str, *, kind: str) -> None:
    if value <= 0:
        raise ContractError(f"[{kind}] {what} must be positive, got {value}")


# ---------------------------------------------------------------------------
# TPU layout rules (Mosaic refuses these; interpret mode runs them anyway)
# ---------------------------------------------------------------------------

SUBLANE = 8  # rows of one (f32) vreg tile
LANE = 128  # lanes of one vreg tile


def check_blocks(
    kind: str, specs: Sequence[tuple[str, tuple[int, ...], tuple[int, ...]]],
) -> None:
    """The (8, 128) block rule for each ``(name, block_shape, array_shape)``:
    a block's last two dims must each be a multiple of (8, 128) or equal the
    array's dim. Leading dims are untiled and unconstrained."""
    for name, block, array in specs:
        for axis, tile in ((-2, SUBLANE), (-1, LANE)):
            if len(block) < -axis:
                continue
            b, a = block[axis], array[axis]
            if b != a and b % tile != 0:
                raise ContractError(
                    f"[{kind}] {name}: block {tuple(block)} over array "
                    f"{tuple(array)} breaks the (8, 128) block rule: dim "
                    f"{axis} is {b}, neither a multiple of {tile} nor the "
                    f"array's {a}\n  hint: pick blocks from "
                    "autotune.heuristic_blocks, which only yields legal ones"
                )


def lane_aligned(width: int, what: str, *, kind: str) -> None:
    """The lane-aligned store rule: a value stored or loaded at a dynamic
    lane offset must span whole 128-lane tiles (Mosaic cannot prove a
    narrower dynamic offset aligned and refuses the access)."""
    divisible(width, LANE, what, kind=kind,
              hint="this width is sliced at a dynamic lane offset in-kernel; "
              "the TPU moves whole 128-lane tiles there")


def _rows(n: int, dtype) -> int:
    """``n`` rounded up to whole sublane tiles of ``dtype`` (8 rows of f32,
    16 of bf16, 32 of int8) — what a small second-minor dim occupies."""
    tile = SUBLANE * 4 // jnp.dtype(dtype).itemsize
    return -(-n // tile) * tile


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane tiles."""
    return -(-n // LANE) * LANE


# ---------------------------------------------------------------------------
# VMEM footprint estimation
# ---------------------------------------------------------------------------

_ROLE_COPIES = {
    # streamed operands and outputs are double-buffered by the Pallas
    # pipeline; pinned (constant-index) operands and scratch live once
    "streamed": 2,
    "out": 2,
    "pinned": 1,
    "scratch": 1,
}


def vmem_footprint(
    bufs: Sequence[tuple[str, tuple[int, ...], object, str]],
) -> tuple[int, dict[str, int]]:
    """Estimate a launch's VMEM working set from its block/scratch shapes.

    ``bufs`` is ``(name, block_shape, dtype, role)`` with role one of
    ``streamed`` / ``pinned`` / ``out`` / ``scratch``. Returns
    ``(total_bytes, {name: bytes})`` with the pipeline's double buffering
    applied to streamed operands and outputs.
    """
    breakdown: dict[str, int] = {}
    for name, shape, dtype, role in bufs:
        copies = _ROLE_COPIES[role]
        nbytes = int(math.prod(shape)) * jnp.dtype(dtype).itemsize * copies
        breakdown[name] = breakdown.get(name, 0) + nbytes
    return sum(breakdown.values()), breakdown


def check_vmem(
    kind: str,
    bufs: Sequence[tuple[str, tuple[int, ...], object, str]],
    budget: Optional[int] = None,
) -> int:
    """Reject an over-budget launch with a per-buffer breakdown BEFORE Mosaic
    produces its allocation error. Returns the estimated total bytes."""
    if budget is None:
        budget = _budget_bytes()
    total, breakdown = vmem_footprint(bufs)
    if total > budget:
        lines = [
            f"    {name:<12} {nbytes / 2**20:8.2f} MiB"
            for name, nbytes in sorted(
                breakdown.items(), key=lambda kv: -kv[1]
            )
        ]
        raise ContractError(
            f"[{kind}] estimated VMEM footprint {total / 2**20:.2f} MiB "
            f"exceeds the {budget / 2**20:.2f} MiB budget "
            "(streamed operands and outputs counted double-buffered):\n"
            + "\n".join(lines)
            + "\n  hint: shrink block_n/block_k (autotune the shape), or let "
            "the dispatch layer route this shape to the jnp oracle"
        )
    return total


# ---------------------------------------------------------------------------
# grid-coverage / divisibility contracts (one per kernel schedule)
# ---------------------------------------------------------------------------


def validate_dual_gemm(
    m: int, n: int, k: int, r: int, group: int, rgroup: int,
    block_m: int, block_n: int, block_k: int,
    *, kind: str = "dual_gemm", budget: Optional[int] = None,
) -> None:
    """Contract for the prefill-shaped dual-component GEMM launch."""
    for name, v in (("block_m", block_m), ("block_n", block_n), ("block_k", block_k)):
        positive(v, name, kind=kind)
    hint = "blocks must tile the padded operand exactly (grid coverage)"
    divisible(m, block_m, "M % block_m", kind=kind, hint=hint)
    divisible(n, block_n, "N % block_n", kind=kind, hint=hint)
    divisible(k, block_k, "K % block_k", kind=kind, hint=hint)
    divisible(block_k, group, "block_k % group", kind=kind,
              hint="every K block must hold whole scale groups")
    divisible(group, 2, "group % 2", kind=kind,
              hint="group-split nibble packing pairs rows inside a group")
    divisible(r, rgroup, "rank % rgroup", kind=kind,
              hint="H requantization tiles the rank axis by rgroup")
    divisible(rgroup, 2, "rgroup % 2", kind=kind,
              hint="V is group-split packed along the rank axis")
    lane_aligned(group, "group % 128", kind=kind)
    check_blocks(kind, [
        ("x", (block_m, block_k), (m, k)),
        ("vp", (r // 2, block_n), (r // 2, n)),
        ("vs", (r // rgroup, block_n), (r // rgroup, n)),
        ("rp", (block_k // 2, block_n), (k // 2, n)),
        ("rs", (k // group, block_n), (k // group, n)),
        ("out", (block_m, block_n), (m, n)),
    ])
    check_vmem(kind, [
        ("x", (block_m, block_k), jnp.bfloat16, "streamed"),
        ("up", (k // 2, r), jnp.int8, "pinned"),
        ("us", (k // group, r), jnp.float32, "pinned"),
        ("vp", (r // 2, block_n), jnp.int8, "streamed"),
        ("vs", (r // rgroup, block_n), jnp.float32, "streamed"),
        ("rp", (block_k // 2, block_n), jnp.int8, "streamed"),
        ("rs", (k // group, block_n), jnp.float32, "streamed"),
        ("out", (block_m, block_n), jnp.bfloat16, "out"),
        ("xq_s", (block_m, k), jnp.int8, "scratch"),
        ("xs_s", (k // block_k, block_m, _lanes(block_k // group)),
         jnp.float32, "scratch"),
        ("h_s", (block_m, r), jnp.float32, "scratch"),
        ("hq_s", (block_m, r), jnp.int8, "scratch"),
        ("hs_s", (block_m, r // rgroup), jnp.float32, "scratch"),
        ("acc_s", (block_m, block_n), jnp.float32, "scratch"),
    ], budget=budget)


def validate_dual_gemv(
    m: int, n: int, k: int, r: int, group: int, rgroup: int, block_n: int,
    *, decode_m_max: int, kind: str = "dual_gemv", budget: Optional[int] = None,
) -> None:
    """Contract for the decode-shaped (resident-panel) dual GEMM launch."""
    positive(block_n, "block_n", kind=kind)
    if m > decode_m_max:
        raise ContractError(
            f"[{kind}] M={m} exceeds the decode panel bound "
            f"DECODE_M_MAX={decode_m_max}\n  hint: the dispatch layer routes "
            "larger M to the prefill schedule"
        )
    divisible(n, block_n, "N % block_n", kind=kind,
              hint="the 1-D grid streams whole (K, block_n) residual tiles")
    divisible(k, group, "K % group", kind=kind,
              hint="the panel is quantized one whole scale group at a time")
    divisible(group, 2, "group % 2", kind=kind,
              hint="group-split nibble packing pairs rows inside a group")
    divisible(r, rgroup, "rank % rgroup", kind=kind,
              hint="H requantization tiles the rank axis by rgroup")
    divisible(rgroup, 2, "rgroup % 2", kind=kind,
              hint="V is group-split packed along the rank axis")
    lane_aligned(group, "group % 128", kind=kind)
    lane_aligned(block_n, "block_n % 128", kind=kind)
    check_blocks(kind, [
        ("rp", (k // 2, block_n), (k // 2, n)),
        ("rs", (k // group, block_n), (k // group, n)),
        ("out", (m, block_n), (m, n)),
    ])
    check_vmem(kind, [
        ("x", (m, k), jnp.bfloat16, "pinned"),
        ("up", (k // 2, r), jnp.int8, "pinned"),
        ("us", (k // group, r), jnp.float32, "pinned"),
        ("vp", (r // 2, n), jnp.int8, "pinned"),
        ("vs", (r // rgroup, n), jnp.float32, "pinned"),
        ("rp", (k // 2, block_n), jnp.int8, "streamed"),
        ("rs", (k // group, block_n), jnp.float32, "streamed"),
        ("out", (m, block_n), jnp.bfloat16, "out"),
        ("xq_s", (m, k), jnp.int8, "scratch"),
        # one (m, 1) slab per group, each a whole padded vreg tile
        ("xs_s", (k // group, _rows(m, jnp.float32), LANE), jnp.float32, "scratch"),
        ("hq_s", (m, r), jnp.int8, "scratch"),
        ("hs_s", (m, r // rgroup), jnp.float32, "scratch"),
    ], budget=budget)


def _validate_segments(
    seg_n: Sequence[int], seg_r: Sequence[int], rgroups: Sequence[int],
    block_n: int, *, kind: str,
) -> None:
    if not (len(seg_n) == len(seg_r) == len(rgroups)):
        raise ContractError(
            f"[{kind}] segment tables disagree: {len(seg_n)} widths, "
            f"{len(seg_r)} ranks, {len(rgroups)} rank-groups"
        )
    for j, (nj, rj, gr) in enumerate(zip(seg_n, seg_r, rgroups)):
        divisible(nj, block_n, f"segment {j}: N_j % block_n", kind=kind,
                  hint="an N block must never straddle a segment boundary")
        divisible(rj, gr, f"segment {j}: rank_j % rgroup_j", kind=kind,
                  hint="each segment's H requantizes with its own rank groups")
        divisible(gr, 2, f"segment {j}: rgroup_j % 2", kind=kind,
                  hint="V is group-split packed along the rank axis")


def validate_dual_gemm_group(
    m: int, k: int, group: int,
    seg_n: Sequence[int], seg_r: Sequence[int], rgroups: Sequence[int],
    block_m: int, block_n: int, block_k: int,
    *, kind: str = "dual_gemm_group", budget: Optional[int] = None,
) -> None:
    """Contract for the prefill-shaped fused sibling-projection launch."""
    for name, v in (("block_m", block_m), ("block_n", block_n), ("block_k", block_k)):
        positive(v, name, kind=kind)
    hint = "blocks must tile the padded operand exactly (grid coverage)"
    divisible(m, block_m, "M % block_m", kind=kind, hint=hint)
    divisible(k, block_k, "K % block_k", kind=kind, hint=hint)
    divisible(block_k, group, "block_k % group", kind=kind,
              hint="every K block must hold whole scale groups")
    divisible(group, 2, "group % 2", kind=kind,
              hint="group-split nibble packing pairs rows inside a group")
    _validate_segments(seg_n, seg_r, rgroups, block_n, kind=kind)
    lane_aligned(group, "group % 128", kind=kind)
    lane_aligned(block_n, "block_n % 128", kind=kind)
    r_total = sum(seg_r)
    n_total = sum(seg_n)
    check_blocks(kind, [
        ("x", (block_m, block_k), (m, k)),
        ("rp", (block_k // 2, block_n), (k // 2, n_total)),
        ("rs", (k // group, block_n), (k // group, n_total)),
        ("out", (block_m, block_n), (m, n_total)),
    ])
    hs_cols = sum(rj // gr for rj, gr in zip(seg_r, rgroups))
    bufs = [
        ("x", (block_m, block_k), jnp.bfloat16, "streamed"),
        ("up", (k // 2, r_total), jnp.int8, "pinned"),
        ("us", (k // group, r_total), jnp.float32, "pinned"),
        ("rp", (block_k // 2, block_n), jnp.int8, "streamed"),
        ("rs", (k // group, block_n), jnp.float32, "streamed"),
        ("out", (block_m, block_n), jnp.bfloat16, "out"),
        ("xq_s", (block_m, k), jnp.int8, "scratch"),
        ("xs_s", (k // block_k, block_m, _lanes(block_k // group)),
         jnp.float32, "scratch"),
        ("h_s", (block_m, r_total), jnp.float32, "scratch"),
        ("hq_s", (block_m, r_total), jnp.int8, "scratch"),
        ("hs_s", (block_m, hs_cols), jnp.float32, "scratch"),
        ("acc_s", (block_m, block_n), jnp.float32, "scratch"),
    ]
    for j, (nj, rj, gr) in enumerate(zip(seg_n, seg_r, rgroups)):
        bufs.append((f"vp[{j}]", (rj // 2, nj), jnp.int8, "pinned"))
        bufs.append((f"vs[{j}]", (rj // gr, nj), jnp.float32, "pinned"))
    check_vmem(kind, bufs, budget=budget)


def validate_dual_gemv_group(
    m: int, k: int, group: int,
    seg_n: Sequence[int], seg_r: Sequence[int], rgroups: Sequence[int],
    block_n: int,
    *, decode_m_max: int, kind: str = "dual_gemv_group",
    budget: Optional[int] = None,
) -> None:
    """Contract for the decode-shaped fused sibling-projection launch."""
    positive(block_n, "block_n", kind=kind)
    if m > decode_m_max:
        raise ContractError(
            f"[{kind}] M={m} exceeds the decode panel bound "
            f"DECODE_M_MAX={decode_m_max}\n  hint: the dispatch layer routes "
            "larger M to the prefill schedule"
        )
    divisible(k, group, "K % group", kind=kind,
              hint="the panel is quantized one whole scale group at a time")
    divisible(group, 2, "group % 2", kind=kind,
              hint="group-split nibble packing pairs rows inside a group")
    _validate_segments(seg_n, seg_r, rgroups, block_n, kind=kind)
    lane_aligned(group, "group % 128", kind=kind)
    lane_aligned(block_n, "block_n % 128", kind=kind)
    r_total = sum(seg_r)
    n_total = sum(seg_n)
    check_blocks(kind, [
        ("rp", (k // 2, block_n), (k // 2, n_total)),
        ("rs", (k // group, block_n), (k // group, n_total)),
        ("out", (m, block_n), (m, n_total)),
    ])
    hs_cols = sum(rj // gr for rj, gr in zip(seg_r, rgroups))
    bufs = [
        ("x", (m, k), jnp.bfloat16, "pinned"),
        ("up", (k // 2, r_total), jnp.int8, "pinned"),
        ("us", (k // group, r_total), jnp.float32, "pinned"),
        ("rp", (k // 2, block_n), jnp.int8, "streamed"),
        ("rs", (k // group, block_n), jnp.float32, "streamed"),
        ("out", (m, block_n), jnp.bfloat16, "out"),
        ("xq_s", (m, k), jnp.int8, "scratch"),
        # one (m, 1) slab per group, each a whole padded vreg tile
        ("xs_s", (k // group, _rows(m, jnp.float32), LANE), jnp.float32, "scratch"),
        ("hq_s", (m, r_total), jnp.int8, "scratch"),
        ("hs_s", (m, hs_cols), jnp.float32, "scratch"),
    ]
    for j, (nj, rj, gr) in enumerate(zip(seg_n, seg_r, rgroups)):
        bufs.append((f"vp[{j}]", (rj // 2, nj), jnp.int8, "pinned"))
        bufs.append((f"vs[{j}]", (rj // gr, nj), jnp.float32, "pinned"))
    check_vmem(kind, bufs, budget=budget)


def validate_w4a16(
    m: int, n: int, k: int, group: int,
    block_m: int, block_n: int, block_k: int,
    *, kind: str = "w4a16_gemm", budget: Optional[int] = None,
) -> None:
    """Contract for the weight-only int4 GEMM launch."""
    for name, v in (("block_m", block_m), ("block_n", block_n), ("block_k", block_k)):
        positive(v, name, kind=kind)
    hint = "blocks must tile the padded operand exactly (grid coverage)"
    divisible(m, block_m, "M % block_m", kind=kind, hint=hint)
    divisible(n, block_n, "N % block_n", kind=kind, hint=hint)
    divisible(k, block_k, "K % block_k", kind=kind, hint=hint)
    divisible(block_k, group, "block_k % group", kind=kind,
              hint="every K block must hold whole scale groups")
    divisible(group, 2, "group % 2", kind=kind,
              hint="group-split nibble packing pairs rows inside a group")
    check_blocks(kind, [
        ("x", (block_m, block_k), (m, k)),
        ("wp", (block_k // 2, block_n), (k // 2, n)),
        ("ws", (k // group, block_n), (k // group, n)),
        ("out", (block_m, block_n), (m, n)),
    ])
    check_vmem(kind, [
        ("x", (block_m, block_k), jnp.bfloat16, "streamed"),
        ("wp", (block_k // 2, block_n), jnp.int8, "streamed"),
        ("ws", (k // group, block_n), jnp.float32, "streamed"),
        ("out", (block_m, block_n), jnp.bfloat16, "out"),
        ("acc_s", (block_m, block_n), jnp.float32, "scratch"),
    ], budget=budget)


def validate_ragged_attention(
    t: int, h: int, kvh: int, hd: int, b: int, maxp: int, page: int,
    *, kind: str = "ragged", budget: Optional[int] = None,
) -> None:
    """Contract for the ragged-attention launch (one mixed prefill/decode
    token batch of T rows attending paged KV pools through block tables).

    The schedule is a (B, maxp) grid whose step (b, j) computes only the
    query tiles that cover slot b's rows, and only while page j holds
    committed keys of slot b. Scalar-prefetched (SMEM): the block tables
    and each slot's first row, last row and committed length. Pinned in
    VMEM: the whole query panel, the in-batch K/V rows, the row metadata,
    the f32 online-softmax state and the output, all row-tiled. Streamed:
    one (page, KV*hd) K/V page pair per grid step, clamped to the slot's
    last live page so the steps past it fetch nothing. So T (the engine
    token budget, padded to whole tiles by the caller) is the knob that
    blows the budget, not the sequence length."""
    positive(t, "T (token batch rows)", kind=kind)
    positive(page, "page_size", kind=kind)
    positive(maxp, "max_pages (block-table width)", kind=kind)
    positive(b, "B (engine slots)", kind=kind)
    divisible(h, kvh, "n_heads % n_kv_heads", kind=kind,
              hint="GQA groups share each KV head across h//kvh query heads")
    check_vmem(kind, [
        ("q", (t, h * hd), jnp.bfloat16, "pinned"),
        ("k_page", (1, page, kvh * hd), jnp.bfloat16, "streamed"),
        ("v_page", (1, page, kvh * hd), jnp.bfloat16, "streamed"),
        ("k_tok", (t, kvh * hd), jnp.bfloat16, "pinned"),
        ("v_tok", (t, kvh * hd), jnp.bfloat16, "pinned"),
        ("meta", (4 * t,), jnp.int32, "pinned"),
        ("out", (t, h * hd), jnp.bfloat16, "out"),
        ("m_s", (t, h), jnp.float32, "scratch"),
        ("l_s", (t, h), jnp.float32, "scratch"),
        ("acc_s", (t, h * hd), jnp.float32, "scratch"),
    ], budget=budget)


def validate_paged_decode(
    b: int, sq: int, h: int, kvh: int, hd: int, maxp: int, page: int,
    *, decode_m_max: int = 8, kind: str = "paged_decode",
    budget: Optional[int] = None,
) -> None:
    """Contract for the paged decode-attention launch (B slots x sq draft
    rows attending paged KV pools through scalar-prefetched block tables,
    with the tail-page commit fused into the epilogue).

    The schedule pins the whole (B*sq, H*hd) query panel, the (B*sq, KV*hd)
    draft K/V rows, the f32 online-softmax state, and the output in VMEM
    while streaming one (page, KV*hd) K/V page pair per grid step (plus the
    tail pages in the commit epilogue) — so B*sq is the knob that blows the
    budget, never the sequence length. ``sq`` is additionally bounded by the
    decode panel regime (speculative verification stacks at most
    DECODE_M_MAX rows per slot, matching the dual-GEMV routing bound)."""
    positive(b, "B (engine slots)", kind=kind)
    positive(sq, "sq (draft rows per slot)", kind=kind)
    positive(page, "page_size", kind=kind)
    positive(maxp, "max_pages (block-table width)", kind=kind)
    if sq > decode_m_max:
        raise ContractError(
            f"[{kind}] sq={sq} draft rows exceed the decode panel bound "
            f"DECODE_M_MAX={decode_m_max}\n  hint: the speculative engine "
            "verifies at most DECODE_M_MAX tokens per slot per launch"
        )
    divisible(h, kvh, "n_heads % n_kv_heads", kind=kind,
              hint="GQA groups share each KV head across h//kvh query heads")
    t2 = b * sq
    check_vmem(kind, [
        ("q", (t2, h * hd), jnp.bfloat16, "pinned"),
        ("k_page", (1, page, kvh * hd), jnp.bfloat16, "streamed"),
        ("v_page", (1, page, kvh * hd), jnp.bfloat16, "streamed"),
        ("k_tok", (t2, kvh * hd), jnp.bfloat16, "pinned"),
        ("v_tok", (t2, kvh * hd), jnp.bfloat16, "pinned"),
        # slot-major (B, sq, KV*hd) view: each slot's rows pad to a whole
        # bf16 sublane tile
        ("k_slot", (b, _rows(sq, jnp.bfloat16), kvh * hd), jnp.bfloat16, "pinned"),
        ("v_slot", (b, _rows(sq, jnp.bfloat16), kvh * hd), jnp.bfloat16, "pinned"),
        ("meta", (t2,), jnp.int32, "pinned"),
        ("out", (t2, h * hd), jnp.bfloat16, "out"),
        ("k_tail", (1, page, kvh * hd), jnp.bfloat16, "out"),
        ("v_tail", (1, page, kvh * hd), jnp.bfloat16, "out"),
        ("m_s", (t2, h), jnp.float32, "scratch"),
        ("l_s", (t2, h), jnp.float32, "scratch"),
        ("acc_s", (t2, h * hd), jnp.float32, "scratch"),
    ], budget=budget)


def check_paged_decode_args(q, kp, vp, kt, vt, bt, pos,
                            *, kind: str = "paged_decode") -> None:
    """Shape/dtype consistency contract for a paged-decode call.

    ``q (B, sq, H, hd)`` / ``kt, vt (B, sq, KV, hd)`` are the draft rows,
    ``kp, vp (P, page, KV, hd)`` the paged pools of ONE layer, ``bt (B,
    maxp)`` the block tables and ``pos (B,)`` the committed prefix lengths.
    Malformed combinations raise before any routing decision is made."""
    problems = []
    if q.ndim != 4:
        problems.append(f"q: expected (B, sq, H, hd), got {tuple(q.shape)}")
    if kt.ndim != 4 or vt.ndim != 4 or kt.shape != vt.shape:
        problems.append(
            f"kt/vt: expected matching (B, sq, KV, hd), got {tuple(kt.shape)} "
            f"vs {tuple(vt.shape)}"
        )
    if kp.ndim != 4 or vp.ndim != 4 or kp.shape != vp.shape:
        problems.append(
            f"kp/vp: expected matching (P, page, KV, hd) pools, got "
            f"{tuple(kp.shape)} vs {tuple(vp.shape)}"
        )
    if bt.ndim != 2:
        problems.append(f"bt: expected (B, max_pages), got {tuple(bt.shape)}")
    if problems:
        raise ContractError(
            f"[{kind}] malformed paged-decode call:\n  " + "\n  ".join(problems)
        )
    b, sq, _, hd = q.shape
    if kt.shape[0] != b or kt.shape[1] != sq or kt.shape[3] != hd:
        problems.append(
            f"kt shape {tuple(kt.shape)} disagrees with q {tuple(q.shape)}"
        )
    if kp.shape[2] != kt.shape[2] or kp.shape[3] != hd:
        problems.append(
            f"pool trailing dims {tuple(kp.shape[2:])} != draft (KV, hd)="
            f"({kt.shape[2]}, {hd})"
        )
    if q.shape[2] % kt.shape[2] != 0:
        problems.append(
            f"n_heads {q.shape[2]} not a multiple of n_kv_heads {kt.shape[2]}"
        )
    if bt.shape[0] != b:
        problems.append(
            f"bt rows {bt.shape[0]} != B={b} slots"
        )
    if pos.shape != (b,):
        problems.append(
            f"pos: expected ({b},), got {tuple(pos.shape)}"
        )
    if problems:
        raise ContractError(
            f"[{kind}] malformed paged-decode call:\n  " + "\n  ".join(problems)
        )


def check_ragged_args(q, kp, vp, kt, vt, bt, slot, pos, ctx,
                      *, kind: str = "ragged") -> None:
    """Shape/dtype consistency contract for a ragged-attention call.

    ``q (T, H, hd)`` / ``kt, vt (T, KV, hd)`` are the current step's rows,
    ``kp, vp (P, page, KV, hd)`` the paged pools of ONE layer, ``bt (B,
    maxp)`` the block tables and ``slot/pos (T,)`` / ``ctx (B,)`` the ragged
    row metadata (slot == B marks a pad row). Malformed combinations raise
    before any routing decision is made."""
    problems = []
    if q.ndim != 3:
        problems.append(f"q: expected (T, H, hd), got {tuple(q.shape)}")
    if kt.ndim != 3 or vt.ndim != 3 or kt.shape != vt.shape:
        problems.append(
            f"kt/vt: expected matching (T, KV, hd), got {tuple(kt.shape)} "
            f"vs {tuple(vt.shape)}"
        )
    if kp.ndim != 4 or vp.ndim != 4 or kp.shape != vp.shape:
        problems.append(
            f"kp/vp: expected matching (P, page, KV, hd) pools, got "
            f"{tuple(kp.shape)} vs {tuple(vp.shape)}"
        )
    if bt.ndim != 2:
        problems.append(f"bt: expected (B, max_pages), got {tuple(bt.shape)}")
    if problems:
        raise ContractError(f"[{kind}] malformed ragged call:\n  " + "\n  ".join(problems))
    t, _, hd = q.shape
    if kt.shape[0] != t or kt.shape[2] != hd:
        problems.append(
            f"kt rows/head_dim {tuple(kt.shape)} disagree with q {tuple(q.shape)}"
        )
    if kp.shape[2] != kt.shape[1] or kp.shape[3] != hd:
        problems.append(
            f"pool trailing dims {tuple(kp.shape[2:])} != in-batch (KV, hd)="
            f"({kt.shape[1]}, {hd})"
        )
    if q.shape[1] % kt.shape[1] != 0:
        problems.append(
            f"n_heads {q.shape[1]} not a multiple of n_kv_heads {kt.shape[1]}"
        )
    if slot.shape != (t,) or pos.shape != (t,):
        problems.append(
            f"slot/pos: expected ({t},), got {tuple(slot.shape)} / {tuple(pos.shape)}"
        )
    if ctx.shape != (bt.shape[0],):
        problems.append(
            f"ctx: expected ({bt.shape[0]},) to match bt rows, got {tuple(ctx.shape)}"
        )
    if problems:
        raise ContractError(f"[{kind}] malformed ragged call:\n  " + "\n  ".join(problems))


# ---------------------------------------------------------------------------
# weight-pack consistency contracts (dispatch entries)
# ---------------------------------------------------------------------------


def _is_int8(a) -> bool:
    return jnp.dtype(a.dtype) == jnp.dtype(jnp.int8)


def _is_float(a) -> bool:
    return jnp.issubdtype(jnp.dtype(a.dtype), jnp.floating)


def check_twinquant_pack(w, k: int, *, kind: str = "dual") -> None:
    """Internal-consistency contract for a :class:`TwinQuantWeights` pack.

    Rejects packs whose field shapes/dtypes disagree with each other or with
    the activation's K — the malformations that previously produced garbage
    numerics or an unexplained ref fallback. Odd-but-consistent shapes (N
    not 128-aligned, K not a group multiple) are ROUTING decisions and pass.
    """
    problems = []
    for name, a, want_int8 in (
        ("up", w.up, True), ("us", w.us, False), ("vp", w.vp, True),
        ("vs", w.vs, False), ("rp", w.rp, True), ("rs", w.rs, False),
    ):
        if a.ndim != 2:
            problems.append(f"{name}: expected a 2-D pack field, got shape {a.shape}")
        if want_int8 and not _is_int8(a):
            problems.append(f"{name}: expected packed int8 nibbles, got {a.dtype}")
        if not want_int8 and not _is_float(a):
            problems.append(f"{name}: expected float scales, got {a.dtype}")
    if problems:
        raise ContractError(f"[{kind}] malformed pack:\n  " + "\n  ".join(problems))
    r, n = w.up.shape[-1], w.rp.shape[-1]
    if w.up.shape[-2] * 2 != k:
        problems.append(
            f"up rows {w.up.shape[-2]} pack K={w.up.shape[-2] * 2}, but the "
            f"activation has K={k}"
        )
    if w.rp.shape[-2] * 2 != k:
        problems.append(
            f"rp rows {w.rp.shape[-2]} pack K={w.rp.shape[-2] * 2}, but the "
            f"activation has K={k}"
        )
    if w.us.shape[-2] * w.group != k:
        problems.append(
            f"us has {w.us.shape[-2]} scale rows for group={w.group}, "
            f"covering K={w.us.shape[-2] * w.group} != {k}"
        )
    if w.us.shape[-1] != r:
        problems.append(f"us width {w.us.shape[-1]} != rank {r}")
    if w.vp.shape[-2] * 2 != r:
        problems.append(
            f"vp rows {w.vp.shape[-2]} pack rank={w.vp.shape[-2] * 2} != {r}"
        )
    if w.vs.shape[-2] * w.rgroup != r:
        problems.append(
            f"vs has {w.vs.shape[-2]} scale rows for rgroup={w.rgroup}, "
            f"covering rank={w.vs.shape[-2] * w.rgroup} != {r}"
        )
    if w.vp.shape[-1] != n or w.vs.shape[-1] != n:
        problems.append(
            f"V width ({w.vp.shape[-1]}, {w.vs.shape[-1]}) != output N={n}"
        )
    if w.rs.shape[-2] * w.group != k or w.rs.shape[-1] != n:
        problems.append(
            f"rs shape {tuple(w.rs.shape)} inconsistent with "
            f"(K/group, N)=({k}/{w.group}, {n})"
        )
    if problems:
        raise ContractError(
            f"[{kind}] malformed pack (K={k}, N={n}, rank={r}, "
            f"group={w.group}, rgroup={w.rgroup}):\n  " + "\n  ".join(problems)
        )


def check_twinquant_group_pack(gw, k: int, *, kind: str = "dual_fused") -> None:
    """Consistency contract for a fused :class:`TwinQuantGroupWeights` pack:
    stacked U/R fields must agree with the per-segment V geometry."""
    problems = []
    if len(gw.vps) != len(gw.vss) or len(gw.vps) != len(gw.rgroups):
        problems.append(
            f"segment tables disagree: {len(gw.vps)} vp, {len(gw.vss)} vs, "
            f"{len(gw.rgroups)} rgroups"
        )
        raise ContractError(f"[{kind}] malformed fused pack:\n  " + "\n  ".join(problems))
    if gw.up.shape[-2] * 2 != k or gw.rp.shape[-2] * 2 != k:
        problems.append(
            f"packed K ({gw.up.shape[-2] * 2} in up, {gw.rp.shape[-2] * 2} in "
            f"rp) != activation K={k}"
        )
    if gw.us.shape[-2] * gw.group != k:
        problems.append(
            f"us has {gw.us.shape[-2]} scale rows for group={gw.group}, "
            f"covering K={gw.us.shape[-2] * gw.group} != {k}"
        )
    if gw.up.shape[-1] != sum(gw.seg_r):
        problems.append(
            f"stacked U rank {gw.up.shape[-1]} != sum of segment ranks "
            f"{sum(gw.seg_r)}"
        )
    if gw.rp.shape[-1] != sum(gw.seg_n):
        problems.append(
            f"concatenated R width {gw.rp.shape[-1]} != sum of segment widths "
            f"{sum(gw.seg_n)}"
        )
    for j, (vp, vs, gr) in enumerate(zip(gw.vps, gw.vss, gw.rgroups)):
        if vp.shape[-1] != vs.shape[-1]:
            problems.append(
                f"segment {j}: vp width {vp.shape[-1]} != vs width {vs.shape[-1]}"
            )
        if vs.shape[-2] * gr != vp.shape[-2] * 2:
            problems.append(
                f"segment {j}: vs rows {vs.shape[-2]} x rgroup {gr} != "
                f"rank {vp.shape[-2] * 2}"
            )
    if problems:
        raise ContractError(
            f"[{kind}] malformed fused pack (K={k}, segments N={gw.seg_n}, "
            f"r={gw.seg_r}):\n  " + "\n  ".join(problems)
        )


def check_w4a16_pack(wp, ws, k: int, group: int, *, kind: str = "w4a16") -> None:
    """Consistency contract for a weight-only (packed, scales) pair."""
    problems = []
    if wp.ndim != 2 or ws.ndim != 2:
        problems.append(f"expected 2-D (wp, ws), got {wp.shape}, {ws.shape}")
    elif not _is_int8(wp):
        problems.append(f"wp: expected packed int8 nibbles, got {wp.dtype}")
    elif not _is_float(ws):
        problems.append(f"ws: expected float scales, got {ws.dtype}")
    else:
        if wp.shape[-2] * 2 != k:
            problems.append(
                f"wp rows {wp.shape[-2]} pack K={wp.shape[-2] * 2}, but the "
                f"activation has K={k}"
            )
        if ws.shape[-2] * group != k:
            problems.append(
                f"ws has {ws.shape[-2]} scale rows for group={group}, "
                f"covering K={ws.shape[-2] * group} != {k}"
            )
        if wp.shape[-1] != ws.shape[-1]:
            problems.append(
                f"wp width {wp.shape[-1]} != ws width {ws.shape[-1]}"
            )
    if problems:
        raise ContractError(
            f"[{kind}] malformed w4a16 pack (K={k}, group={group}):\n  "
            + "\n  ".join(problems)
        )
