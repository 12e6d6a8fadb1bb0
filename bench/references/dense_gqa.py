"""Plain reference of a dense pre-norm GQA decoder with W4A4 TwinQuant
linears, written from the published architectures (Phi-3/Phi-4-mini and
Qwen2 ``modeling`` code in Hugging Face ``transformers``) and from TwinQuant's
W4A4 linear, in ``jax.numpy`` with every product at ``HIGHEST`` precision. No kernels,
no cache, no batching: one causal pass over a whole token sequence, layer by
layer, weights drawn anew from the seed (``harness/model.py``).

Layer (pre-norm, as published):
    h = rmsnorm(x) * g1;  q, k, v = W4A4(h) (+ bias, Qwen2);  rope(q, k)
    x = x + W4A4_o(softmax(q k^T / sqrt(hd)) v)
    h = rmsnorm(x) * g2;  x = x + W4A4_down(silu(W4A4_gate(h)) * W4A4_up(h))
logits = rmsnorm(x) * gf . E^T (tied embedding E).

W4A4 linear (TwinQuant): the activation row is quantized per group of
``group`` inputs to 4 bits (scale amax/7, round to nearest, clip to +-7);
``y = Xq R + Hq V`` with ``H = Xq U`` requantized per group of ``rgroup``
rank columns the same way. Each product is an integer GEMM of 4-bit codes
whose per-group partials are scaled in f32: exact on any backend, with no
dependence on a float matmul's precision.

Precision. The published models run in bf16. Every value a bf16 model
stores (normed activations, projections, rotated q/k, attention outputs,
the residual stream) is rounded to ``store`` at the point where the model
stores it (``jax.lax.reduce_precision``); arithmetic between those points is
f32. ``store="bfloat16"`` is the reference; ``"float8_e4m3fn"`` is the
lower-precision control.

Departures from the published models, shared with the program under test:
rotary pairs are interleaved (dims 2i, 2i+1) rather than split in halves, an
equivalent layout under a fixed permutation of q/k output channels, which a
checkpoint conversion applies; Phi-4-mini's LongRoPE short factors are not
applied (plain RoPE over the rotated 75% of each head).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import load, model as M

A = load.sibling(__file__, "architectures", "dense_gqa")

HI = jax.lax.Precision.HIGHEST  # every product at f32 accuracy, also on a TPU
QBLOCK = 512  # query rows per attention block (bounds the score matrix)
BUCKET = 512  # sequences are padded to a multiple of this (causal: no effect)


def _quant(x, group: int):
    """Symmetric 4-bit quantization of x (M, K) along K in groups: int8 codes
    round(x / s), clipped to +-7, and one f32 scale s = amax / 7 per group
    (1 for an all-zero group)."""
    m, k = x.shape
    g = x.reshape(m, k // group, group).astype(jnp.float32)
    amax = jnp.max(jnp.abs(g), axis=2)
    scale = jnp.where(amax > 0, amax / M.QMAX, 1.0)
    q = jnp.clip(jnp.round(g / scale[:, :, None]), -M.QMAX, M.QMAX)
    return q.reshape(m, k).astype(jnp.int8), scale


def _int_gemm(xq, xs, w, ws):
    """sum over groups g of (Xq_g . W_g) * sx_g * sw_g: 4-bit codes multiply
    exactly in int32, each group's partial is scaled in f32, groups are
    summed in ascending order. xq (S, K) int8, xs (S, K/G), w (K, N) int8,
    ws (K/G, N)."""
    s, k = xq.shape
    ng, n = ws.shape
    g = k // ng
    xg = xq.reshape(s, ng, g).transpose(1, 0, 2)
    wg = w.reshape(ng, g, n)

    def body(acc, i):
        p = jax.lax.dot_general(xg[i], wg[i], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return acc + p.astype(jnp.float32) * xs[:, i][:, None] * ws[i][None, :], None

    acc, _ = jax.lax.scan(body, jnp.zeros((s, n), jnp.float32), jnp.arange(ng))
    return acc


def _draw_layer(lkey, arch: A.Arch, layer) -> dict:
    w = A.draw_layer(lkey, arch, layer)
    out = {"ln1": w["ln1"].astype(jnp.float32), "ln2": w["ln2"].astype(jnp.float32)}
    for name in arch.linears():
        lin = dict(w[name])
        if "b" in lin:
            lin["b"] = lin["b"].astype(jnp.float32)
        out[name] = lin
    return out


def _linear(p: dict, x, arch: A.Arch, rnd):
    """TwinQuant W4A4: y = Xq R + requant(Xq U) V, all integer GEMMs with
    per-group scales; the output is stored, then the bias added."""
    k = x.shape[-1]
    xq, xs = _quant(x, min(arch.group, k))
    h = _int_gemm(xq, xs, p["u"], p["us"])
    hq, hs = _quant(h, min(arch.group, h.shape[-1]))
    y = rnd(_int_gemm(xq, xs, p["r"], p["rs"]) + _int_gemm(hq, hs, p["v"], p["vs"]))
    if "b" in p:
        y = rnd(y + p["b"])
    return y


def _rmsnorm(x, g, eps, rnd):
    n = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return rnd(rnd(n) * rnd(g))


def _rope(x, pos, arch: A.Arch, rnd):
    """x (S, H, hd), pos (S,): rotate the first ``rope_fraction`` of each
    head in interleaved pairs; cos/sin are stored like any bf16 value."""
    hd = x.shape[-1]
    rot = int(hd * arch.rope_fraction) // 2 * 2
    if rot == 0:
        return x
    freqs = 1.0 / (arch.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = rnd(jnp.cos(ang))[:, None, :], rnd(jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y1 = rnd(rnd(x1 * c) - rnd(x2 * s))
    y2 = rnd(rnd(x2 * c) + rnd(x1 * s))
    yr = jnp.stack([y1, y2], axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([yr, x[..., rot:]], axis=-1)


def _attention(q, k, v, arch: A.Arch, rnd):
    """Causal GQA over a whole sequence, f32 softmax, query blocks."""
    s, h, hd = q.shape
    g = h // arch.n_kv_heads
    kk = jnp.repeat(k, g, axis=1)  # (S, H, hd)
    vv = jnp.repeat(v, g, axis=1)
    qb = q.reshape(s // QBLOCK, QBLOCK, h, hd)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, kk, precision=HI) * (hd ** -0.5)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        sc = jnp.where(jnp.arange(s)[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    out = jax.lax.map(block, (jnp.arange(s // QBLOCK), qb))
    return rnd(out.reshape(s, h, hd))


def rounding(store):
    """Rounding to ``store`` (a bits pair: exponent, mantissa) as an explicit
    op: a cast down and back up may be dropped by a compiler that allows
    excess precision, as XLA does on a TPU."""
    exp_bits, mant_bits = STORES[store]
    return lambda a: jax.lax.reduce_precision(a, exponent_bits=exp_bits,
                                              mantissa_bits=mant_bits)


STORES = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}


@functools.partial(jax.jit, static_argnames=("arch", "store"))
def _layer(w: dict, x, arch: A.Arch, store):
    rnd = rounding(store)

    s = x.shape[0]
    pos = jnp.arange(s)
    h, kvh, hd = arch.n_heads, arch.n_kv_heads, arch.head_dim
    a = _rmsnorm(x, w["ln1"], arch.norm_eps, rnd)
    q = _linear(w["q"], a, arch, rnd).reshape(s, h, hd)
    k = _linear(w["k"], a, arch, rnd).reshape(s, kvh, hd)
    v = _linear(w["v"], a, arch, rnd).reshape(s, kvh, hd)
    q, k = _rope(q, pos, arch, rnd), _rope(k, pos, arch, rnd)
    o = _linear(w["o"], _attention(q, k, v, arch, rnd).reshape(s, h * hd), arch, rnd)
    x = rnd(x + o)
    a = _rmsnorm(x, w["ln2"], arch.norm_eps, rnd)
    act = rnd(rnd(jax.nn.silu(_linear(w["gate"], a, arch, rnd))) * _linear(w["up"], a, arch, rnd))
    return rnd(x + _linear(w["down"], act, arch, rnd))


_draw = jax.jit(_draw_layer, static_argnames=("arch",))


@functools.partial(jax.jit, static_argnames=("arch", "store"))
def _logits(x, rows, gf, table, arch: A.Arch, store):
    """Logits over the real vocabulary at ``rows`` of the final states
    ``x``; ``table`` is the (padded_vocab, D) lm head."""
    rnd = rounding(store)

    h = _rmsnorm(x[rows], gf.astype(jnp.float32), arch.norm_eps, rnd)
    return jnp.matmul(h, rnd(table[: arch.vocab].astype(jnp.float32)).T, precision=HI)


def final_states(seed: int, arch: A.Arch, seqs: list, stores: tuple) -> dict:
    """The residual stream after the last layer of each sequence in ``seqs``
    (1-D int arrays, padded to a multiple of ``BUCKET``), for every
    precision in ``stores`` (names in STORES): {store: [array (S, D)]}.
    Weights are drawn once per layer and shared by all sequences."""
    key = M.root_key(seed)
    embed = A.draw_embed(key, arch)
    xs = {}
    for st in stores:
        xs[st] = []
        for seq in seqs:
            n = len(seq)
            s_pad = -(-n // BUCKET) * BUCKET
            tok = np.zeros(s_pad, np.int32)
            tok[:n] = seq
            x0 = embed[jnp.asarray(tok)].astype(jnp.float32)
            xs[st].append(rounding(st)(x0))
    for layer in range(arch.n_layers):
        w = _draw(M.layer_key(key, layer), arch, layer)
        for st in stores:
            xs[st] = [_layer(w, x, arch, st) for x in xs[st]]
        del w
    return xs


def head_logits(xs: dict, rows: list, gf, table, arch: A.Arch) -> dict:
    """{store: [logits (len(rows[i]), vocab)]} of ``final_states``' output
    through the final norm ``gf`` and the lm head ``table``."""
    return {st: [np.asarray(_logits(x, jnp.asarray(r, jnp.int32), gf, table, arch, st))
                 for x, r in zip(xs[st], rows)]
            for st in xs}


def logits_at(seed: int, arch: A.Arch, seqs: list, rows: list, stores: tuple) -> dict:
    """Reference logits (f32, over the real vocabulary) of each sequence in
    ``seqs`` (1-D int arrays) at the positions ``rows[i]``, for every
    precision in ``stores`` (names in STORES): {store: [array (len(rows[i]),
    vocab)]}. The lm head is the embedding (tied)."""
    xs = final_states(seed, arch, seqs, stores)
    key = M.root_key(seed)
    return head_logits(xs, rows, A.draw_final_norm(key, arch), A.draw_embed(key, arch), arch)
