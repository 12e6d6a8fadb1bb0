"""Operations and bytes that one call of a kernel needs, computed from the
pack shapes and from the rows that carry a token. Pad rows, which the
program may compute anyway, are never counted, and weights are counted once
per call; a later change that stops computing pad rows raises a roofline
share honestly and cannot push it past 100%.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class Cost:
    ops: float  # arithmetic operations (a multiply-add is two)
    bytes: float  # HBM bytes the call has to move at least once

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.ops + other.ops, self.bytes + other.bytes)

    def ideal_s(self, peak_ops: float, peak_bytes: float) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.ops / peak_ops, self.bytes / peak_bytes)


def pack_bytes(k: int, n: int, r: int, group: int, rgroup: int) -> float:
    """HBM bytes of one dual-component pack: three 4-bit factors (two codes
    a byte) and their f32 scales."""
    codes = (k * n + k * r + r * n) / 2
    scales = 4 * ((k // group) * n + (k // group) * r + (r // rgroup) * n)
    return codes + scales


def dual_gemm(m: int, k: int, segments: Sequence[tuple], group: int) -> Cost:
    """One (fused) dual-component linear over ``m`` token rows.

    ``segments`` lists ``(n, r, rgroup)`` for each sibling projection that
    shares the activation (one entry for an unfused linear). Integer work per
    row: X.R (K.N), X.U (K.r) and H.V (r.N) multiply-adds. Bytes: every pack
    once, the bf16 activation rows in and the bf16 output rows out.
    """
    ops = 0.0
    by = 2.0 * m * k
    for n, r, rgroup in segments:
        ops += 2.0 * m * (k * n + k * r + r * n)
        by += pack_bytes(k, n, r, group, rgroup) + 2.0 * m * n
    return Cost(ops, by)


def ragged_attention(keys_per_row: Sequence[int], ctx_per_slot: Sequence[int],
                     n_heads: int, n_kv_heads: int, head_dim: int) -> Cost:
    """One ragged attention call.

    ``keys_per_row`` holds, for each row that carries a token, the number of
    keys it attends (its position + 1: cached prefix plus the in-batch causal
    prefix). ``ctx_per_slot`` holds each scheduled slot's committed cache
    rows, which the call has to read once. Operations: QK and PV, two
    multiply-adds each per key, head and head dim. Bytes: committed K and V
    (bf16), the rows' q, k, v in and the output out.
    """
    keys = float(sum(keys_per_row))
    rows = len(keys_per_row)
    ops = 4.0 * keys * n_heads * head_dim
    kv_row = 2 * n_kv_heads * head_dim * 2  # K and V, bf16
    by = float(sum(ctx_per_slot)) * kv_row
    by += rows * (2 * n_heads * head_dim * 2 + kv_row)  # q and out, k and v
    return Cost(ops, by)


def lm_head(rows: int, d_model: int, vocab: int) -> Cost:
    """Logits of ``rows`` rows over a bf16 (vocab, d_model) table, tied to
    the embedding or not: the table once, f32 logits out."""
    return Cost(2.0 * rows * d_model * vocab, 2.0 * vocab * d_model + 4.0 * rows * vocab)
