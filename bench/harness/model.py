"""The benchmark's seeded model: every weight of a dense GQA decoder, drawn
from ``--seed`` on the device, in the form W4A4 TwinQuant serves it.

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
each later layer's two writers (``o`` and ``down``) at ``OUT_GAIN`` of that.
A W4A4 linear quantizes its input to 4 bits, so a rounding difference far
below bf16's step flips whole quanta; with full-scale updates at every layer
those flips grow through 28-32 layers until any two sound implementations,
and a float8 one, give unrelated tokens (PERF.md, "How correct is decided").

This module is the one source of weights. The program gets them packed
(``harness/program.py``); the plain reference dequantizes them itself
(``references/``). Neither reads the other's arrays.

Everything here is pure ``jax.numpy`` and imports nothing of the program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

QMAX = 7  # symmetric 4-bit
CODE_STD = 2.5  # std of the normal the codes are rounded from
SCALE_DIV = 2.75  # the codes' std once clipped, edged with +-7 and rounded,
                  # with the spread that power-of-two scales add
EMBED_STD = 0.02
NORM_LOG_STD = 0.1  # norm gains are exp(N(0, 0.1^2))
BIAS_STD = 0.5
OUT_GAIN = 1 / 32  # std of a later layer's o and down outputs, against the first's
OUT_LINEARS = ("o", "down")  # the linears that write into the residual stream


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the generator and the reference need, read from a
    configuration file (``bench/configs/<name>.json``)."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    qkv_bias: bool
    rope_theta: float
    rope_fraction: float
    norm_eps: float
    rank: int
    group: int
    a_bits: int

    def linears(self) -> dict:
        """name -> (parent, K, N, has_bias), in a fixed order."""
        d, h, kv, hd, f = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff
        return {
            "q": ("attn", d, h * hd, self.qkv_bias),
            "k": ("attn", d, kv * hd, self.qkv_bias),
            "v": ("attn", d, kv * hd, self.qkv_bias),
            "o": ("attn", h * hd, d, False),
            "gate": ("mlp", d, f, False),
            "up": ("mlp", d, f, False),
            "down": ("mlp", f, d, False),
        }

    def rank_of(self, k: int, n: int) -> int:
        r = min(self.rank, k // 2, n)
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


def draw_linear(key, arch: Arch, k: int, n: int, bias: bool, gain=1.0) -> dict:
    """One dual-component linear: codes and scales of R, U and V (+ bias)."""
    r = arch.rank_of(k, n)
    g = min(arch.group, k)
    rg = min(arch.group, r)
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


def draw_layer(lkey, arch: Arch, layer) -> dict:
    """Every weight of decoder layer ``layer`` (an int or a traced index):
    {linear name: draw_linear(...), "ln1": (D,), "ln2": (D,)} (norm gains
    bf16)."""
    out = {}
    out_gain = jnp.where(layer == 0, 1.0, OUT_GAIN)
    for i, (name, (_, k, n, bias)) in enumerate(arch.linears().items()):
        gain = out_gain if name in OUT_LINEARS else 1.0
        out[name] = draw_linear(jax.random.fold_in(lkey, i), arch, k, n, bias, gain)
    kn = jax.random.fold_in(lkey, 100)
    k1, k2 = jax.random.split(kn)
    d = arch.d_model
    out["ln1"] = jnp.exp(NORM_LOG_STD * jax.random.normal(k1, (d,))).astype(jnp.bfloat16)
    out["ln2"] = jnp.exp(NORM_LOG_STD * jax.random.normal(k2, (d,))).astype(jnp.bfloat16)
    return out


def draw_embed(seed_key, arch: Arch) -> jax.Array:
    """Tied embedding / lm head (padded_vocab, D) bf16."""
    k = jax.random.fold_in(seed_key, 2)
    return (jax.random.normal(k, (arch.padded_vocab, arch.d_model)) * EMBED_STD).astype(
        jnp.bfloat16
    )


def draw_final_norm(seed_key, arch: Arch) -> jax.Array:
    k = jax.random.fold_in(seed_key, 3)
    return jnp.exp(NORM_LOG_STD * jax.random.normal(k, (arch.d_model,))).astype(jnp.bfloat16)
