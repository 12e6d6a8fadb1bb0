"""Shared model substrate: norms, RoPE, linears (with quantized dispatch),
GQA attention with KV cache, embedding/init helpers.

Parameters are plain nested dicts of jnp arrays (scan-stacked per layer).
Every matmul in the network goes through :func:`linear`, which dispatches on
the parameter keys:

    {"w" [, "b"]}                          -> bf16 dense
    {"wp", "ws" [, "b"]}                   -> W4A16 weight-only (packed int4)
    {"up","us","vp","vs","rp","rs" [,"b"]} -> TwinQuant dual-component W4A4/W4A8

so TwinQuant is a first-class precision mode of the whole framework, not a
bolt-on — quantize_model() rewrites the params pytree and every architecture
(dense/MoE/MLA/SSM/...) picks it up through this one dispatcher.

Sibling projections that consume the SAME activation (q/k/v, gate/up,
wq_a/wkv_a) go through :func:`linear_group`, which merges packed
dual-component siblings into ONE fused launch (kernels/dispatch.fused_linear)
— either from a pre-merged pack produced by ``core.twinquant.fuse_params``
(key ``qkv`` / ``gate_up`` / ``wqkv_a``; checkpoints stay unfused on disk) or
by fusing the sibling packs at trace time — and falls back to per-sibling
:func:`linear` for every other precision mode.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# logits tap + finite guard (serving fault tolerance)
# ---------------------------------------------------------------------------

# Host-side observation/injection point for freshly-downloaded logits. The
# serving engine routes every sync-point logits download through
# ``logits_tap`` so fault-injection harnesses (launch/faults.py) can corrupt
# one slot's row deterministically, and checks ``nonfinite_rows`` right after
# to quarantine slots whose logits went NaN/Inf. Identity (zero-cost) unless
# a tap is installed.
_logits_tap: Optional[Callable] = None


def set_logits_tap(fn: Optional[Callable]) -> Optional[Callable]:
    """Install ``fn(last, tag) -> last`` as the host logits tap (``None`` to
    remove). ``last`` is the host np.ndarray just downloaded at a sync point;
    ``tag`` names the call site (``"prefill"`` / ``"decode"`` / ``"ragged"``).
    Returns the previously-installed tap so callers can restore it."""
    global _logits_tap
    prev = _logits_tap
    _logits_tap = fn
    return prev


def logits_tap(last: np.ndarray, tag: str) -> np.ndarray:
    """Route a freshly-downloaded host logits array through the installed
    tap, if any. Called by the engine at every sync-point download."""
    if _logits_tap is None:
        return last
    return _logits_tap(last, tag)


def nonfinite_rows(last: np.ndarray, vocab: int) -> list:
    """Indices of rows of ``last (..., V)`` holding any NaN/Inf inside the
    first ``vocab`` columns (padded tail columns are ignored). The engine's
    finite-logits guard: a non-empty result quarantines those slots."""
    finite = np.isfinite(last[..., :vocab]).all(axis=-1)
    return [int(i) for i in np.flatnonzero(~finite.reshape(-1))]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, bias: bool = False, scale: float = 1.0):
    std = scale / (d_in**0.5)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * std).astype(DTYPE)}
    if bias:
        p["b"] = jnp.zeros((d_out,), DTYPE)
    return p


def embed_init(key, vocab: int, d: int):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(DTYPE)


def _cs(x: jax.Array, *spec_dims) -> jax.Array:
    """Context-aware sharding constraint (no-op without a mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.context import get_mesh_context

    ctx = get_mesh_context()
    if ctx.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, P(*spec_dims)))


def embed_attend(embed: jax.Array) -> jax.Array:
    """Constrain the embedding table at its use site. Without this the SPMD
    partitioner materializes replicated f32 embed gradients when the table is
    used by both the input gather and a tied head (measured 4x temp blowup —
    EXPERIMENTS §Perf iteration log)."""
    from repro.models.context import get_mesh_context

    ctx = get_mesh_context()
    if ctx.mesh is None:
        return embed
    fsdp = tuple(ctx.fsdp_axes) or None
    return _cs(embed, ctx.tp_axis, fsdp)


def embed_lookup(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    """Sharded token-embedding gather: (B, S) -> (B, S, D)."""
    from repro.models.context import get_mesh_context

    ctx = get_mesh_context()
    x = embed_attend(embed)[tokens]
    if ctx.mesh is None:
        return x
    dp = tuple(ctx.dp_axes) or None
    return _cs(x, dp, *([None] * (x.ndim - 1)))


# ---------------------------------------------------------------------------
# linear dispatch (bf16 / w4a16 / twinquant)
# ---------------------------------------------------------------------------


def linear(p: dict, x: jax.Array) -> jax.Array:
    """Apply a (possibly quantized) linear layer; x: (..., K) -> (..., N)."""
    if "w" in p:
        y = jnp.einsum("...k,kn->...n", x, p["w"].astype(x.dtype))
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y
    if "r_dq" in p:  # quantized-numerics simulation (benchmarks; exact W4Ax math)
        from repro.core.quantization import QuantConfig, fake_quant

        xh = x / p["lam"].astype(x.dtype)
        if "Q" in p:
            xh = jnp.einsum("...k,kq->...q", xh, p["Q"].astype(x.dtype))
        a_bits = p["abits"].shape[-1]
        if a_bits < 16:
            k = xh.shape[-1]
            xh = fake_quant(xh, QuantConfig(bits=a_bits, group_size=min(128, k), axis=-1))
        w_eff = p["r_dq"].astype(x.dtype)
        y = jnp.einsum("...k,kn->...n", xh, w_eff)
        if "u_dq" in p:
            h = jnp.einsum("...k,kr->...r", xh, p["u_dq"].astype(x.dtype))
            if a_bits < 16:  # H requantization (the fused kernel's s_H step)
                r = h.shape[-1]
                h = fake_quant(h, QuantConfig(bits=a_bits, group_size=min(128, r), axis=-1))
            y = y + jnp.einsum("...r,rn->...n", h, p["v_dq"].astype(x.dtype))
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y
    if "rp" in p:  # TwinQuant dual-component pack
        from repro.kernels.dispatch import quant_linear

        # routed by shape regime (prefill / decode / ref) at trace time; on
        # CPU the routed schedule executes with oracle numerics (dispatch.py)
        return quant_linear(x, weights_of(p), p.get("b")).astype(x.dtype)
    if "wp" in p:  # W4A16 weight-only pack
        from repro.kernels.dispatch import w4a16_linear

        return w4a16_linear(
            x, p["wp"], p["ws"], p.get("b"),
            group=p["wp"].shape[-2] * 2 // p["ws"].shape[-2],
        ).astype(x.dtype)
    raise KeyError(f"unrecognized linear params: {sorted(p)}")


def weights_of(p: dict):
    """Pack param dict ({up,us,vp,vs,rp,rs,abits}) -> :class:`TwinQuantWeights`.

    Static metadata is encoded in (static) shapes: scale-group sizes from
    packed-vs-scale row ratios, activation bits from the ``abits`` marker
    array's length — keeps the params pytree jit-pure."""
    from repro.kernels.ref import TwinQuantWeights

    return TwinQuantWeights(
        up=p["up"], us=p["us"], vp=p["vp"], vs=p["vs"], rp=p["rp"], rs=p["rs"],
        group=p["rp"].shape[-2] * 2 // p["rs"].shape[-2],
        rgroup=p["vp"].shape[-2] * 2 // p["vs"].shape[-2],
        a_bits=p["abits"].shape[-1],
    )


def group_weights_of(fp: dict):
    """Fused-pack param dict ({up,us,rp,rs,abits,vp0,vs0,...}) -> group pack.

    Like :func:`weights_of`, all static metadata is recovered from (static)
    array shapes so the params pytree stays jit-pure.
    """
    from repro.kernels.ref import TwinQuantGroupWeights

    vps, vss = [], []
    while f"vp{len(vps)}" in fp:
        vps.append(fp[f"vp{len(vps)}"])
        vss.append(fp[f"vs{len(vss)}"])
    return TwinQuantGroupWeights(
        up=fp["up"], us=fp["us"], vps=tuple(vps), vss=tuple(vss),
        rp=fp["rp"], rs=fp["rs"],
        group=fp["rp"].shape[-2] * 2 // fp["rs"].shape[-2],
        rgroups=tuple(
            vp.shape[-2] * 2 // vs.shape[-2] for vp, vs in zip(vps, vss)
        ),
        a_bits=fp["abits"].shape[-1],
    )


def _fusable_packs(ps) -> bool:
    """Sibling param dicts that can merge into one fused launch: all packed
    dual-component (unstacked at this call site), same K, scale group, and
    activation bits — derived from static shapes only."""
    if not all(isinstance(pp, dict) and "rp" in pp for pp in ps):
        return False
    base = ps[0]
    group = base["rp"].shape[-2] * 2 // base["rs"].shape[-2]
    return all(
        pp["rp"].ndim == 2
        and pp["rp"].shape[0] == base["rp"].shape[0]
        and pp["rp"].shape[-2] * 2 // pp["rs"].shape[-2] == group
        and pp["abits"].shape == base["abits"].shape
        for pp in ps
    )


def linear_group(p: dict, names: tuple, fused_key: str, x: jax.Array) -> tuple:
    """Apply sibling projections of ONE activation as a fused launch.

    Resolution order:
      1. ``p[fused_key]`` exists (quantization-time pack merging via
         ``core.twinquant.fuse_params`` — checkpoints stay unfused on disk,
         the in-memory tree carries the merged pack): one fused launch.
         This is the serving configuration (the engine pre-merges).
      2. the siblings ``p[name]`` are fusable dual-component packs and
         fusion is enabled: fuse at trace time and launch once. The
         concatenation runs INSIDE the traced step (packs are jit arguments,
         not constants), so this path pays an extra copy of each fused
         weight pack per execution — correct everywhere, but hot loops
         should pre-merge with ``fuse_params`` instead.
      3. otherwise (bf16, w4a16, sim dicts, mixed precision, fusion
         disabled): one :func:`linear` per sibling — the pre-fusion path.
         ``set_fusion(False)`` also forces a pre-merged pack (case 1) to
         execute per segment, so the A/B toggle is honest for both layouts.

    Returns one output per sibling, in ``names`` order.
    """
    from repro.kernels.dispatch import fused_linear, fusion_enabled, quant_linear

    fp = p.get(fused_key)
    if fp is not None:
        gw = group_weights_of(fp)
        biases = gw.split(fp["b"]) if "b" in fp else (None,) * gw.n_segments
        if not fusion_enabled():  # A/B lane: per-segment launches
            return tuple(
                quant_linear(x, gw.segment(j), biases[j]).astype(x.dtype)
                for j in range(gw.n_segments)
            )
        return tuple(
            y.astype(x.dtype) for y in fused_linear(x, gw, biases)
        )
    ps = [p[n] for n in names]
    if fusion_enabled() and _fusable_packs(ps):
        ys = fused_linear(x, [weights_of(pp) for pp in ps],
                          biases=[pp.get("b") for pp in ps])
        return tuple(y.astype(x.dtype) for y in ys)
    return tuple(linear(pp, x) for pp in ps)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    norm = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return norm * w.astype(x.dtype) + b.astype(x.dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


# ---------------------------------------------------------------------------
# RoPE (partial-fraction aware)
# ---------------------------------------------------------------------------


def rope_tables(positions: jax.Array, head_dim: int, fraction: float, theta: float):
    """cos/sin tables for the rotated sub-dimension. positions: (...,)"""
    rot = int(head_dim * fraction) // 2 * 2
    if rot == 0 or theta <= 0:
        return None
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., rot/2)
    return jnp.cos(ang), jnp.sin(ang), rot


def apply_rope(x: jax.Array, tables) -> jax.Array:
    """x: (B, S, H, hd); tables from rope_tables with positions (B, S)."""
    if tables is None:
        return x
    cos, sin, rot = tables
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    y1 = x1 * c - x2 * s
    y2 = x2 * c + x1 * s
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1) if xp.shape[-1] else yr


# ---------------------------------------------------------------------------
# GQA attention (train / prefill / decode-with-cache)
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask) -> jax.Array:
    """q: (B,Sq,H,hd), k: (B,Sk,KV,hd), v: (B,Sk,KV,hd_v); GQA via head
    grouping; qk and v head dims may differ (MLA). f32 softmax."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    logits = logits / (hd**0.5)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


# memory-efficient causal attention: never materializes the (Sq, Sk) score
# matrix — online-softmax over KV blocks (flash-attention recurrence), with
# fully-masked blocks skipped via lax.cond. Used by every train/prefill path
# once S exceeds _ATTN_CHUNK; without it the 4k/32k shapes need O(S^2) temp
# (hundreds of GB/device at 32k — see EXPERIMENTS.md §Perf iteration log).
_ATTN_CHUNK = 512


def _shard_heads(x: jax.Array, head_axis: int) -> jax.Array:
    """Constrain an attention tensor's head dim over the TP axis (when it
    divides) and its batch dim over dp. Without this the SPMD partitioner
    re-gathers the full stacked K/V per flash step (measured 12 TB/device on
    deepseek prefill — §Perf cell B iteration 1)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.context import get_mesh_context

    ctx = get_mesh_context()
    if ctx.mesh is None or ctx.tp_axis is None:
        return x
    tp = ctx.mesh.shape[ctx.tp_axis]
    spec = [None] * x.ndim
    dp = tuple(ctx.dp_axes)
    dpn = 1
    for a in dp:
        dpn *= ctx.mesh.shape[a]
    if dp and x.shape[0] % dpn == 0:
        spec[0] = dp
    if x.shape[head_axis] % tp == 0:
        spec[head_axis] = ctx.tp_axis
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, P(*spec)))


def _sdpa_causal_chunked(q, k, v, chunk: int = _ATTN_CHUNK) -> jax.Array:
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    hv = v.shape[-1]
    if s % chunk != 0 or s <= chunk:
        causal = jnp.tril(jnp.ones((s, s), bool))[None]
        return _sdpa(q, k, v, causal)
    n = s // chunk
    scale = hd**-0.5
    q = _shard_heads(q, 2)
    k = _shard_heads(k, 2)
    v = _shard_heads(v, 2)
    qb = (q * scale).reshape(b, n, chunk, kv, g, hd)
    kb = _shard_heads(k.reshape(b, n, chunk, kv, hd), 3)
    vb = _shard_heads(v.reshape(b, n, chunk, kv, hv), 3)

    def q_block(_, qi_and_q):
        qi, qq = qi_and_q  # qq (B, cq, KV, G, hd)

        def kv_step(carry, kj_and_kv):
            kj, kk, vv = kj_and_kv

            def compute(carry):
                m, l, acc = carry
                logits = jnp.einsum("bqkgh,bskh->bkgqs", qq, kk).astype(jnp.float32)
                qpos = qi * chunk + jnp.arange(chunk)
                kpos = kj * chunk + jnp.arange(chunk)
                causal = qpos[:, None] >= kpos[None, :]
                logits = jnp.where(causal[None, None, None], logits, -1e30)
                m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
                p = jnp.exp(logits - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bkgqs,bskh->bkgqh", p.astype(vv.dtype), vv
                ).astype(jnp.float32)
                return m_new, l_new, acc_new

            carry = jax.lax.cond(kj <= qi, compute, lambda c: c, carry)
            return carry, None

        init = (
            jnp.full((b, kv, g, chunk), -1e30, jnp.float32),
            jnp.zeros((b, kv, g, chunk), jnp.float32),
            jnp.zeros((b, kv, g, chunk, hv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init,
            (jnp.arange(n), kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, KV, G, cq, hv)
        return None, out.transpose(0, 3, 1, 2, 4).astype(v.dtype)  # (B, cq, KV, G, hv)

    _, blocks = jax.lax.scan(
        q_block, None, (jnp.arange(n), qb.transpose(1, 0, 2, 3, 4, 5))
    )
    # blocks: (n, B, cq, KV, G, hv)
    return blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hv)


def sdpa_causal(q, k, v) -> jax.Array:
    """Causal attention, memory-efficient for long sequences."""
    return _sdpa_causal_chunked(q, k, v)


def attention_train(p: dict, x: jax.Array, cfg: ModelConfig, positions=None,
                    segment_mask: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence causal attention (training / prefill)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = linear_group(p, ("q", "k", "v"), "qkv", x)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if positions is None:
        positions = jnp.arange(s)[None, :].astype(jnp.int32) * jnp.ones((b, 1), jnp.int32)
    tables = rope_tables(positions, hd, cfg.rope_fraction, cfg.rope_theta)
    q = apply_rope(q, tables)
    k = apply_rope(k, tables)
    if segment_mask is not None:
        causal = jnp.tril(jnp.ones((s, s), bool))[None] & segment_mask
        out = _sdpa(q, k, v, causal)
    else:
        out = sdpa_causal(q, k, v)
    return linear(p["o"], out.reshape(b, s, h * hd))


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=DTYPE) -> dict:
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((n_layers, batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, kvh, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# paged KV cache (DESIGN.md §14)
#
# The dense decode state keeps one (..., B, S_max, ...) cache per slot, so
# memory scales with slots x worst-case length. The paged layout replaces
# every sequence-carrying leaf with a global page pool
# (..., n_pages, page_size, ...) shared by all slots, plus one per-slot block
# table ``bt (B, max_pages)`` of page indices (-1 = unmapped). Decode gathers
# a slot's pages through its block-table row and scatters the new token into
# the slot's tail page; the host-side allocator in launch/serve.py owns the
# free list / refcounts. Which leaves become pools is decided STRUCTURALLY
# (paged_layout): a leaf whose shape changes with max_len carries the
# sequence axis and gets paged; everything else (recurrent SSM/xLSTM states,
# encoder K/V, pos) stays per-slot, exactly like the engine's slot-axis
# inference.
# ---------------------------------------------------------------------------


def paged_layout(init_fn, cfg: ModelConfig, max_len: int) -> dict:
    """Classify decode-state leaves: key -> (slot_axis, seq_axis | None).

    The slot axis comes from a batch-2 vs batch-1 ``eval_shape`` diff, the
    sequence axis from a max_len vs 2*max_len diff. Page pools require the
    canonical (..., B, S, ...) layout (seq axis right after the slot axis) —
    every family in the registry satisfies it, and a violation fails loudly
    here rather than corrupting pages later.
    """
    s2 = jax.eval_shape(lambda: init_fn(cfg, 2, max_len))
    s1 = jax.eval_shape(lambda: init_fn(cfg, 1, max_len))
    sl = jax.eval_shape(lambda: init_fn(cfg, 1, 2 * max_len))
    if not isinstance(s1, dict):
        raise TypeError("paged serving requires a flat dict decode state")
    out = {}
    for key in s1:
        slot = [i for i, (a, b) in enumerate(zip(s2[key].shape, s1[key].shape)) if a != b]
        seq = [i for i, (a, b) in enumerate(zip(s1[key].shape, sl[key].shape)) if a != b]
        if len(slot) != 1 or len(seq) > 1:
            raise ValueError(f"cannot classify state leaf {key!r}: "
                             f"{s2[key].shape} vs {s1[key].shape} vs {sl[key].shape}")
        # the paged runtime hard-codes (lead, B, S, ...) for pools: slot axis
        # 1, seq axis 2 (page writer / token scatter index the pool at axis 1)
        if seq and (slot[0] != 1 or seq[0] != 2):
            raise ValueError(f"page pools need (lead, B, S, ...) layout, got "
                             f"{key!r} with slot axis {slot[0]}, seq axis {seq[0]}")
        out[key] = (slot[0], seq[0] if seq else None)
    return out


def init_paged_state(init_fn, cfg: ModelConfig, batch: int, max_len: int,
                     page_size: int, n_pages: int) -> dict:
    """Paged decode state: sequence-carrying leaves become global page pools
    (lead, n_pages, page_size, trail); per-slot leaves are kept verbatim; a
    block table ``bt (B, ceil(max_len/page_size))`` maps slot timelines to
    pages. Families with no sequence leaves (pure recurrent state) get their
    dense state back unchanged — there is nothing to page."""
    layout = paged_layout(init_fn, cfg, max_len)
    st = dict(init_fn(cfg, batch, max_len))
    pooled = False
    for key, (slot, seq) in layout.items():
        if seq is None:
            continue
        sh = st[key].shape
        st[key] = jnp.zeros(sh[:slot] + (n_pages, page_size) + sh[seq + 1:], st[key].dtype)
        pooled = True
    if pooled:
        max_pages = -(-max_len // page_size)
        st["bt"] = jnp.full((batch, max_pages), -1, jnp.int32)
    return st


def gather_pages(pool_l: jax.Array, bt: jax.Array) -> jax.Array:
    """One layer's pool (P, page, ...) + block table (B, maxp) -> the dense
    per-slot view (B, maxp*page, ...). Unmapped (-1) entries read page 0;
    callers mask those rows with the per-slot ``pos`` prefix mask, exactly as
    the dense path masks rows >= pos."""
    b, maxp = bt.shape
    pages = pool_l[jnp.maximum(bt, 0)]  # (B, maxp, page, ...)
    return pages.reshape(b, maxp * pool_l.shape[1], *pool_l.shape[2:])


def scatter_token_pages(pool: jax.Array, t: jax.Array, bt: jax.Array,
                        pos: jax.Array) -> jax.Array:
    """Scatter each slot's one-token line into its tail page.

    pool (lead, P, page, ...), t (lead, B, 1, ...), bt (B, maxp), pos (B,).
    The target is page ``bt[b, pos_b // page]`` row ``pos_b % page``; slots
    whose target is unmapped (bt -1, e.g. an evicted slot decoding garbage in
    lock-step) or past the block table are dropped, mirroring the dense
    path's drop-not-clamp rule. The invalid sentinel is ``n_pages`` (one past
    the pool), NOT -1: negative indices are canonicalized NumPy-style before
    ``mode="drop"`` applies, so -1 would silently wrap into the LAST page and
    corrupt whichever slot owns it."""
    page = pool.shape[2]
    n_pages = pool.shape[1]
    b, maxp = bt.shape
    pi = pos // page
    page_id = bt[jnp.arange(b), jnp.minimum(pi, maxp - 1)]
    page_id = jnp.where((pi < maxp) & (page_id >= 0), page_id, n_pages)
    return pool.at[:, page_id, pos % page].set(t[:, :, 0].astype(pool.dtype), mode="drop")


def scatter_rows_pages(pool: jax.Array, t: jax.Array, bt: jax.Array,
                       slot: jax.Array, pos: jax.Array) -> jax.Array:
    """Scatter a ragged step's T rows into their slots' pages.

    pool (lead, P, page, ...), t (lead, T, ...), bt (B, maxp), slot (T,)
    with pad sentinel >= B, pos (T,). Row ``i`` lands in page
    ``bt[slot_i, pos_i // page]`` at offset ``pos_i % page``; pad rows,
    rows past the block table, and unmapped pages are dropped through the
    same ``n_pages`` OOB sentinel as :func:`scatter_token_pages` (-1 would
    wrap into the last page)."""
    page = pool.shape[2]
    n_pages = pool.shape[1]
    b, maxp = bt.shape
    pi = pos // page
    page_id = bt[jnp.clip(slot, 0, b - 1), jnp.minimum(pi, maxp - 1)]
    ok = (slot < b) & (pi < maxp) & (page_id >= 0)
    page_id = jnp.where(ok, page_id, n_pages)
    return pool.at[:, page_id, pos % page].set(t.astype(pool.dtype), mode="drop")


def ragged_attn(p: dict, h: jax.Array, cfg: ModelConfig, kp: jax.Array,
                vp: jax.Array, bt: jax.Array, slot: jax.Array,
                pos: jax.Array, ctx: jax.Array):
    """One layer's attention over a ragged mixed prefill/decode token batch.

    ``h (1, T, D)`` holds every live request's scheduled tokens for this
    engine step, flat; ``slot/pos (T,)`` map each row to its engine slot and
    absolute position (``slot == B`` is padding), ``ctx (B,)`` is each
    slot's committed cache length and ``kp/vp (P, page, KV, hd)`` are one
    layer's page pools behind the block tables ``bt (B, maxp)``. The fused
    q/k/v group launch runs ONCE over all T rows (prefill chunks and decode
    tokens share it — the engine-level analog of the dual-GEMM fusion), then
    the routed ragged-attention kernel attends cache prefix + same-slot
    in-batch causal prefix. Returns (out (1, T, D), k_t (T, KV, hd), v_t)
    with k_t/v_t post-RoPE, ready for the page scatter."""
    from repro.kernels.dispatch import ragged_attention

    _, t, _ = h.shape
    hh, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = linear_group(p, ("q", "k", "v"), "qkv", h)
    q = q.reshape(1, t, hh, hd)
    k = k.reshape(1, t, kvh, hd)
    v = v.reshape(1, t, kvh, hd)
    tables = rope_tables(pos[None, :], hd, cfg.rope_fraction, cfg.rope_theta)
    q = apply_rope(q, tables)
    k = apply_rope(k, tables)
    out = ragged_attention(q[0], kp, vp, k[0], v[0], bt, slot, pos, ctx)
    return linear(p["o"], out.reshape(1, t, hh * hd)), k[0], v[0]


def paged_attn(p: dict, x: jax.Array, cfg: ModelConfig, kp: jax.Array,
               vp: jax.Array, bt: jax.Array, pos: jax.Array):
    """One layer's decode attention straight over paged KV pools.

    ``x (B, sq, D)`` holds each slot's decode rows (``sq == 1`` plain decode,
    ``sq > 1`` speculative draft stacks), ``kp/vp (P, page, KV, hd)`` one
    layer's page pools behind the block tables ``bt (B, maxp)``, and ``pos
    (B,)`` each slot's committed prefix length. Routes the in-kernel
    block-table path (``kernels/dispatch.paged_decode``): pages stream
    through the kernel, so no dense ``gather_pages`` view of the cache is
    ever materialized. Returns (out (B, sq, D), k_t (B, sq, KV, hd), v_t)
    with k_t/v_t post-RoPE, ready for the caller's post-scan page commit
    (``commit=False`` — the scan-stacked families batch one scatter per
    layer after the scan)."""
    from repro.kernels.dispatch import paged_decode

    b, sq, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = linear_group(p, ("q", "k", "v"), "qkv", x)
    q = q.reshape(b, sq, h, hd)
    k = k.reshape(b, sq, kvh, hd)
    v = v.reshape(b, sq, kvh, hd)
    positions = slot_positions(pos, b, sq)
    tables = rope_tables(positions, hd, cfg.rope_fraction, cfg.rope_theta)
    q = apply_rope(q, tables)
    k = apply_rope(k, tables)
    out = paged_decode(q, kp, vp, k, v, bt, pos, commit=False)
    return linear(p["o"], out.reshape(b, sq, h * hd)), k, v


def select_at_length(x: jax.Array, length) -> jax.Array:
    """Last REAL position of each row: x (B, S, D), length (B,) or scalar ->
    (B, 1, D). ``length=None`` means the whole row is real (no padding)."""
    if length is None:
        return x[:, -1:]
    idx = jnp.clip(jnp.asarray(length, jnp.int32).reshape(-1) - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def prefill_pos(length, batch: int, s: int) -> jax.Array:
    """Per-slot position vector after a prefill of s (possibly padded) tokens
    of which ``length`` are real."""
    if length is None:
        return jnp.full((batch,), s, jnp.int32)
    return jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (batch,))


def gate_state_update(new_state: dict, old_state: dict, valid: jax.Array,
                      b_axis: dict) -> dict:
    """Keep each slot's state update only where ``valid`` (B,) is True —
    bucketed prefill gates recurrent-state updates off for pad steps.

    ``b_axis`` maps each state key to its slot (batch) axis as a NEGATIVE
    offset from the trailing dims, which is uniform across a family's
    stacked-layout variants (e.g. mamba_hybrid's n_seg/rest groupings)."""
    out = {}
    for key, new in new_state.items():
        ax = b_axis[key] % new.ndim
        shape = [1] * new.ndim
        shape[ax] = valid.shape[0]
        out[key] = jnp.where(valid.reshape(shape), new, old_state[key])
    return out


def prefix_attn_mask(s: int, off: int) -> jax.Array:
    """(1, s, off+s) mask for suffix prefill over a cached prefix: every
    suffix query sees the whole prefix plus the causal part of the suffix."""
    return jnp.concatenate(
        [jnp.ones((1, s, off), bool), jnp.tril(jnp.ones((s, s), bool))[None]], axis=-1
    )


def gqa_prefill_attn(p: dict, h: jax.Array, cfg: ModelConfig, positions: jax.Array,
                     prefix_kv=None, mask=None):
    """One layer's prefill attention (fused q/k/v projection + RoPE), causal
    or — given ``prefix_kv`` = (pk (B, m, KV, hd), pv) from cached pages plus
    the matching ``prefix_attn_mask`` — over [prefix; causal suffix].
    Returns (attn_out, k, v); shared by the dense-style families' prefill
    bodies so the prefix-cache suffix path exists exactly once."""
    b, s, _ = h.shape
    hh, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = linear_group(p, ("q", "k", "v"), "qkv", h)
    q = q.reshape(b, s, hh, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    tables = rope_tables(positions, hd, cfg.rope_fraction, cfg.rope_theta)
    q = apply_rope(q, tables)
    k = apply_rope(k, tables)
    if prefix_kv is None:
        att = sdpa_causal(q, k, v)
    else:
        pk, pv = prefix_kv
        kf = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        vf = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        att = _sdpa(q, kf, vf, mask)
    return linear(p["o"], att.reshape(b, s, hh * hd)), k, v


def slot_positions(pos: jax.Array, b: int, sq: int = 1) -> jax.Array:
    """Per-slot decode positions (B, sq) from a per-slot ``pos`` vector (B,).

    A scalar ``pos`` (legacy single-sequence callers) broadcasts to all slots.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((b,), pos, jnp.int32)
    return pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]


def update_cache_slot(cache: jax.Array, t: jax.Array, pos: jax.Array) -> jax.Array:
    """Scatter a one-token slice at each slot's own offset.

    cache (B, S, ...), t (B, 1, ...), pos (B,). Out-of-range positions
    (a slot past its max_len) are dropped, not clamped, so an overflowing
    slot can never corrupt row S-1."""
    b = cache.shape[0]
    return cache.at[jnp.arange(b), pos].set(t[:, 0].astype(cache.dtype), mode="drop")


def update_cache_slot_stacked(cache: jax.Array, t: jax.Array, pos: jax.Array) -> jax.Array:
    """Layer-stacked variant: cache (L, B, S, ...), t (L, B, 1, ...), pos (B,)."""
    b = cache.shape[1]
    return cache.at[:, jnp.arange(b), pos].set(t[:, :, 0].astype(cache.dtype), mode="drop")


def attention_decode_ro(p: dict, x: jax.Array, cfg: ModelConfig, k_cache, v_cache,
                        pos: jax.Array):
    """Read-only-cache decode attention (§Perf optimization).

    ``pos`` is a per-slot position vector (B,) — every batch slot carries its
    own timeline, so sequences of different lengths (continuous batching)
    decode in lock-step without sharing a global step counter. Each slot
    attends over its own cache prefix [0, pos_b) plus the current token.

    The naive formulation updates the cache INSIDE the layer scan, which
    makes the scan write every layer's full (B, S, KV, hd) cache slice back
    per token (2 x cache bytes of HBM write traffic per step). Here the scan
    reads the cache read-only and attends over [cache(<pos), current token];
    the caller batches ONE one-token scatter per layer after the scan.
    Returns (out, k_t, v_t)."""
    b, sq, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, kt, vt = linear_group(p, ("q", "k", "v"), "qkv", x)
    q = q.reshape(b, sq, h, hd)
    kt = kt.reshape(b, sq, kvh, hd)
    vt = vt.reshape(b, sq, kvh, hd)
    positions = slot_positions(pos, b, sq)
    pos_v = positions[:, 0]  # (B,)
    tables = rope_tables(positions, hd, cfg.rope_fraction, cfg.rope_theta)
    q = apply_rope(q, tables)
    kt = apply_rope(kt, tables)

    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    s_max = k_cache.shape[1]
    logits_c = jnp.einsum("bskgh,btkh->bkgst", qg, k_cache).astype(jnp.float32)
    logits_c = logits_c / (hd**0.5)
    # strict per-slot prefix mask: self handled below
    mask = jnp.arange(s_max)[None, None, None, None, :] < pos_v[:, None, None, None, None]
    logits_c = jnp.where(mask, logits_c, -1e30)
    logit_s = jnp.einsum("bskgh,bskh->bkgs", qg, kt).astype(jnp.float32)[..., None] / (hd**0.5)
    m = jnp.maximum(jnp.max(logits_c, axis=-1, keepdims=True), logit_s)
    pc = jnp.exp(logits_c - m)
    ps = jnp.exp(logit_s - m)
    den = jnp.sum(pc, axis=-1, keepdims=True) + ps
    out = jnp.einsum("bkgst,btkh->bskgh", (pc / den).astype(v_cache.dtype), v_cache)
    self_w = (ps / den)[..., 0][..., None].transpose(0, 3, 1, 2, 4).astype(vt.dtype)
    out = out + self_w * vt[:, :, :, None, :]
    out = out.reshape(b, sq, h, hd)
    return linear(p["o"], out.reshape(b, sq, h * hd)), kt, vt


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, f: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d, f),
        "up": dense_init(k2, d, f),
        "down": dense_init(k3, f, d),
    }


def mlp_apply(p: dict, x: jax.Array) -> jax.Array:
    gate, up = linear_group(p, ("gate", "up"), "gate_up", x)
    return linear(p["down"], swiglu(gate, up))


def attn_init(key, cfg: ModelConfig, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "q": dense_init(k1, d, h * hd, bias=cfg.qkv_bias),
        "k": dense_init(k2, d, kvh * hd, bias=cfg.qkv_bias),
        "v": dense_init(k3, d, kvh * hd, bias=cfg.qkv_bias),
        "o": dense_init(k4, h * hd, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Mean CE over tokens; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def _shard_logits(x: jax.Array) -> jax.Array:
    """Constrain chunk logits to (dp, None, model) — without this the SPMD
    partitioner replicates the f32 logits over the model axis (measured:
    2 full-vocab copies = 40 GB/device at 4k seq; EXPERIMENTS §Perf)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.context import get_mesh_context

    ctx = get_mesh_context()
    if ctx.mesh is None or ctx.tp_axis is None:
        return x
    spec = P(tuple(ctx.dp_axes) or None, None, ctx.tp_axis)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


_CE_CHUNK = 256


def cross_entropy_chunked(hidden: jax.Array, labels: jax.Array, unembed_fn,
                          chunk: int = _CE_CHUNK) -> jax.Array:
    """Memory-bounded CE: unembed + log-softmax one sequence-chunk at a time
    (rematerialized in backward), so full-sequence f32 logits never exist.

    hidden: (B, S, D) post-final-norm; unembed_fn: (B, c, D) -> (B, c, V).
    """
    b, s, d = hidden.shape
    if s % chunk != 0 or s <= chunk:
        logits = _shard_logits(unembed_fn(hidden).astype(jnp.float32))
        return cross_entropy(logits, labels, 0)
    n = s // chunk
    hs = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        xc, lc = xs
        logits = _shard_logits(unembed_fn(xc).astype(jnp.float32))
        mask = lc >= 0
        safe = jnp.where(mask, lc, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return (carry[0] + jnp.sum(nll * mask), carry[1] + jnp.sum(mask)), None

    body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hs, ls)
    )
    return tot / jnp.maximum(cnt, 1)
