"""Seeded W4A4 weights, the parts every architecture shares: a PRNG key
from ``--seed``, one key per layer, and one dual-component linear drawn on
the device in the form W4A4 TwinQuant serves it. Each architecture
(``bench/architectures/<name>.py``) draws its own tree from these.

Each eligible linear ``y = x W (+ b)`` is the dual-component weight
``W = R + U V`` with R (K, N) and U (K, r) in 4-bit codes with one f32 scale
per group of ``group`` rows and column, and V (r, N) in 4-bit codes grouped
by ``rgroup = min(group, r)`` rows. Codes are a rounded normal clipped to
[-7, 7]; the first row of every scale group holds +-7 and scales are powers
of two, so a round-to-nearest quantizer with scale ``amax / 7`` recovers
codes and scales exactly (the program's packer is such a quantizer). Scales
are set so that ``x W`` keeps about the variance of ``x``: half of it through
R, half through U V. That keeps activations finite through the full depth.

The residual stream dominates every layer's update, as in trained decoders
whose stream grows with depth: the first layer writes it at full scale, and
each later layer's writers into the stream at ``OUT_GAIN`` of that. A W4A4
linear quantizes its input to 4 bits, so a rounding difference far below
bf16's step flips whole quanta; with full-scale updates at every layer those
flips grow through 28-32 layers until any two sound implementations, and a
float8 one, give unrelated tokens (PERF.md, "How correct is decided").

The architecture's module is the one source of its weights. The program gets
them packed (``harness/program.py``); the plain reference dequantizes them
itself (``references/``). Neither reads the other's arrays.

Everything here is pure ``jax.numpy`` and imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QMAX = 7  # symmetric 4-bit
CODE_STD = 2.5  # std of the normal the codes are rounded from
SCALE_DIV = 2.75  # the codes' std once clipped, edged with +-7 and rounded,
                  # with the spread that power-of-two scales add
BIAS_STD = 0.5
OUT_GAIN = 1 / 32  # std of a later layer's writes into the residual stream,
                   # against the first layer's


def rank_of(rank: int, k: int, n: int) -> int:
    """The rank the program's quantizer gives a (k, n) linear under the
    configured ``rank``: even, at least 2, at most k / 2 and n."""
    r = min(rank, k // 2, n)
    return max(2, r // 2 * 2)


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low and high 32 bits are folded
    in separately, so seeds past 2**32 stay distinct."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def _factor(key, k: int, n: int, group: int, std: float):
    """(codes int8 (k, n), scales f32 (k / group, n)) of one 4-bit factor
    whose dequantized entries have standard deviation about ``std``."""
    kc, ks, kf = jax.random.split(key, 3)
    codes = jnp.clip(jnp.round(jax.random.normal(kc, (k, n)) * CODE_STD), -QMAX, QMAX)
    edge = jnp.where(jax.random.normal(kf, (k // group, n)) >= 0, QMAX, -QMAX)
    codes = codes.reshape(k // group, group, n).at[:, 0, :].set(edge).reshape(k, n)
    scales = std / SCALE_DIV * jnp.exp(0.2 * jax.random.normal(ks, (k // group, n)))
    # powers of two: 7 * scale is exact, and amax / 7 gives the scale back
    # exactly whether it is divided or multiplied by a rounded 1/7
    scales = jnp.exp2(jnp.round(jnp.log2(scales)))
    return codes.astype(jnp.int8), scales.astype(jnp.float32)


def dequant(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """f32 (k, n) from codes (k, n) and per-group scales (k / g, n)."""
    k, n = codes.shape
    g = k // scales.shape[0]
    return (codes.reshape(k // g, g, n).astype(jnp.float32) * scales[:, None, :]).reshape(k, n)


def draw_linear(key, k: int, n: int, rank: int, group: int, bias: bool, gain=1.0) -> dict:
    """One dual-component linear (k, n) under the configured ``rank`` and
    scale ``group``: codes and scales of R, U and V (+ bias)."""
    r = rank_of(rank, k, n)
    g = min(group, k)
    rg = min(group, r)
    kr, ku, kv, kb = jax.random.split(key, 4)
    out = {}
    out["r"], out["rs"] = _factor(kr, k, n, g, gain * (0.5 / k) ** 0.5)
    out["u"], out["us"] = _factor(ku, k, r, g, (1.0 / k) ** 0.5)
    out["v"], out["vs"] = _factor(kv, r, n, rg, gain * (0.5 / r) ** 0.5)
    if bias:
        out["b"] = (jax.random.normal(kb, (n,)) * BIAS_STD).astype(jnp.bfloat16)
    return out


def layer_key(seed_key, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)
