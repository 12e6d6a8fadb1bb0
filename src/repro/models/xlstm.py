"""xLSTM: chunkwise-parallel mLSTM blocks + recurrent sLSTM blocks.

mLSTM (matrix memory, exponential gating) is computed in the stabilized
chunkwise form for train/prefill — a scan over chunks carrying
(C (dqk,dv), n (dqk,), m (log-stabilizer)) per head, with attention-like
intra-chunk computation — and in the O(1) recurrent form for decode. sLSTM
(scalar memory with block-diagonal recurrence) is inherently sequential and
scans over time, which is why the architecture uses it sparsely
(``slstm_every``). Layers are grouped into segments of
(slstm_every-1 mLSTM + 1 sLSTM) so both stacks scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import common as C

# chunk-size choice (§Perf cell A): per-token state traffic scales as
# H*dqk*dv/chunk while intra-chunk compute/bytes scale as chunk — for the
# 1.3b dims the crossover is ~1k, so long sequences use 1024-token chunks
CHUNK = 1024
CHUNK_MIN = 256


def _pick_chunk(s: int) -> int:
    return CHUNK if s % CHUNK == 0 and s >= CHUNK else CHUNK_MIN


def _dims(cfg: ModelConfig):
    d_inner = int(cfg.d_model * cfg.xlstm_proj_factor)
    h = cfg.n_heads
    return d_inner, h, d_inner // h


def _qk_dim(cfg: ModelConfig) -> int:
    # official xLSTM uses qk_dim_factor 0.5 (halves the matrix-memory state)
    d_inner, h, dh = _dims(cfg)
    return max(2, dh // 2)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _mlstm_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner, h, dh = _dims(cfg)
    k = jax.random.split(key, 8)
    return {
        "ln": jnp.ones((d,), C.DTYPE),
        "up": C.dense_init(k[0], d, 2 * d_inner),
        "conv": (jax.random.normal(k[1], (4, d_inner)) * 0.1).astype(C.DTYPE),
        "wq": C.dense_init(k[2], d_inner, _qk_dim(cfg) * h),
        "wk": C.dense_init(k[3], d_inner, _qk_dim(cfg) * h),
        "wv": C.dense_init(k[4], d_inner, d_inner),
        "wif": C.dense_init(k[5], d_inner, 2 * h),  # input+forget gates per head
        "gn": jnp.ones((d_inner,), C.DTYPE),
        "down": C.dense_init(k[6], d_inner, d),
    }


def _slstm_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    k = jax.random.split(key, 4)
    return {
        "ln": jnp.ones((d,), C.DTYPE),
        "w": C.dense_init(k[0], d, 4 * d),  # i, f, z, o pre-activations
        "r": (jax.random.normal(k[1], (h, dh, 4 * dh)) * (1.0 / dh**0.5)).astype(C.DTYPE),
        "gn": jnp.ones((d,), C.DTYPE),
        "ln2": jnp.ones((d,), C.DTYPE),
        "ffn": C.mlp_init(k[2], d, 2 * d),
    }


def _segments(cfg: ModelConfig) -> tuple[int, int]:
    if cfg.slstm_every <= 0:
        return 0, cfg.n_layers
    n_seg = cfg.n_layers // cfg.slstm_every
    m_per = cfg.slstm_every - 1
    assert n_seg * cfg.slstm_every == cfg.n_layers, "n_layers % slstm_every != 0"
    return n_seg, m_per


def init_params(cfg: ModelConfig, key) -> dict:
    ke, km, ks, kh = jax.random.split(key, 4)
    n_seg, m_per = _segments(cfg)
    p = {"embed": C.embed_init(ke, cfg.padded_vocab, cfg.d_model),
         "ln_f": jnp.ones((cfg.d_model,), C.DTYPE),
         "head": C.dense_init(kh, cfg.d_model, cfg.padded_vocab)}
    if n_seg == 0:
        keys = jax.random.split(km, cfg.n_layers)
        p["m_layers"] = jax.vmap(lambda k: _mlstm_init(k, cfg))(keys)
    else:
        mkeys = jax.random.split(km, n_seg * m_per).reshape(n_seg, m_per, 2)
        p["m_layers"] = jax.vmap(jax.vmap(lambda k: _mlstm_init(k, cfg)))(mkeys)
        p["s_layers"] = jax.vmap(lambda k: _slstm_init(k, cfg))(jax.random.split(ks, n_seg))
    return p


# ---------------------------------------------------------------------------
# mLSTM core (chunkwise, stabilized)
# ---------------------------------------------------------------------------


def _causal_conv(x: jax.Array, w: jax.Array, state=None):
    """Depthwise causal conv, kernel 4. x: (B, S, D); w: (4, D)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state  # (B, k-1, D) from previous steps
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(k))
    new_state = xp[:, -(k - 1) :, :]
    return out, new_state


def _mlstm_chunkwise(q, k, v, i_raw, f_raw, state):
    """q,k: (B, S, H, dqk); v: (B, S, H, dv); i_raw,f_raw: (B, S, H).

    state: dict(C (B,H,dqk,dv), n (B,H,dqk), m (B,H)).
    """
    b, s, h, dqk = q.shape
    dv = v.shape[-1]
    l = _pick_chunk(s)
    nc = s // l
    scale = 1.0 / (dqk**0.5)
    qc = (q * scale).reshape(b, nc, l, h, dqk).astype(jnp.float32)
    kc = k.reshape(b, nc, l, h, dqk).astype(jnp.float32)
    vc = v.reshape(b, nc, l, h, dv).astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_raw.astype(jnp.float32)).reshape(b, nc, l, h)
    ii = i_raw.astype(jnp.float32).reshape(b, nc, l, h)

    def chunk_step(carry, xs):
        Cst, nst, mst = carry  # (B,H,dh,dh), (B,H,dh), (B,H)
        qq, kk, vv, lff, iii = xs  # (B,l,H,dh) etc.
        F = jnp.cumsum(lff, axis=1)  # (B,l,H) inclusive decay-to-t
        # intra-chunk log weights D[t,s] = F_t - F_s + i_s (s<=t)
        Dlog = F[:, :, None, :] - F[:, None, :, :] + iii[:, None, :, :]  # (B,t,s,H)
        tri = jnp.tril(jnp.ones((l, l), bool))[None, :, :, None]
        Dlog = jnp.where(tri, Dlog, -jnp.inf)
        b_t = F + mst[:, None, :]  # (B,l,H) inter-chunk log coefficient
        m_t = jnp.maximum(jnp.max(Dlog, axis=2), b_t)  # (B,l,H)
        m_t = jax.lax.stop_gradient(m_t)
        w_intra = jnp.exp(Dlog - m_t[:, :, None, :])  # (B,t,s,H)
        c_inter = jnp.exp(b_t - m_t)  # (B,l,H)

        scores = jnp.einsum("blhd,bshd->blsh", qq, kk) * w_intra
        num = jnp.einsum("blsh,bshd->blhd", scores, vv)
        num = num + c_inter[..., None] * jnp.einsum("blhd,bhde->blhe", qq, Cst)
        den = jnp.sum(scores, axis=2) + c_inter * jnp.einsum("blhd,bhd->blh", qq, nst)
        hout = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]

        # state update
        g = F[:, -1]  # (B,H) total chunk decay
        wk = jnp.exp(g[:, None, :] - F + iii)  # (B,l,H) per-key weight (unstab.)
        m_new = jnp.maximum(g + mst, jnp.max(jnp.log(jnp.maximum(wk, 1e-38)), axis=1))
        m_new = jax.lax.stop_gradient(m_new)
        wk_st = jnp.exp(g[:, None, :] - F + iii - m_new[:, None, :])
        decay = jnp.exp(g + mst - m_new)
        C_new = decay[:, :, None, None] * Cst + jnp.einsum("blhd,blhe,blh->bhde", kk, vv, wk_st)
        n_new = decay[:, :, None] * nst + jnp.einsum("blhd,blh->bhd", kk, wk_st)
        return (C_new, n_new, m_new), hout

    xs = (
        qc.transpose(1, 0, 2, 3, 4), kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
        lf.transpose(1, 0, 2, 3), ii.transpose(1, 0, 2, 3),
    )
    (Cst, nst, mst), hs = jax.lax.scan(chunk_step, (state["C"], state["n"], state["m"]), xs)
    h_out = hs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)
    return h_out.astype(q.dtype), {"C": Cst, "n": nst, "m": mst}


def _mlstm_step(q, k, v, i_raw, f_raw, state):
    """Single-token recurrent mLSTM. q,k: (B,1,H,dqk); v: (B,1,H,dv)."""
    b, _, h, dqk = q.shape
    scale = 1.0 / (dqk**0.5)
    qq = (q[:, 0] * scale).astype(jnp.float32)
    kk = k[:, 0].astype(jnp.float32)
    vv = v[:, 0].astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_raw[:, 0].astype(jnp.float32))  # (B,H)
    ii = i_raw[:, 0].astype(jnp.float32)
    m_new = jnp.maximum(lf + state["m"], ii)
    f_st = jnp.exp(lf + state["m"] - m_new)
    i_st = jnp.exp(ii - m_new)
    C_new = f_st[:, :, None, None] * state["C"] + i_st[:, :, None, None] * jnp.einsum(
        "bhd,bhe->bhde", kk, vv
    )
    n_new = f_st[:, :, None] * state["n"] + i_st[:, :, None] * kk
    num = jnp.einsum("bhd,bhde->bhe", qq, C_new)
    den = jnp.einsum("bhd,bhd->bh", qq, n_new)
    hout = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return hout[:, None].astype(q.dtype), {"C": C_new, "n": n_new, "m": m_new}


def _mlstm_block(lp, x, cfg, state=None, conv_state=None, step=False):
    """Full mLSTM block. Returns (out, new_state, new_conv_state)."""
    d_inner, h, dh = _dims(cfg)
    b, s, _ = x.shape
    hin = C.rmsnorm(x, lp["ln"], cfg.norm_eps)
    up = C.linear(lp["up"], hin)
    xm, z = up[..., :d_inner], up[..., d_inner:]
    xc, conv_state = _causal_conv(xm, lp["conv"], conv_state)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    dqk = _qk_dim(cfg)
    q = C.linear(lp["wq"], xc).reshape(b, s, h, dqk)
    k = C.linear(lp["wk"], xc).reshape(b, s, h, dqk)
    v = C.linear(lp["wv"], xm).reshape(b, s, h, dh)
    gates = C.linear(lp["wif"], xc).reshape(b, s, h, 2)
    i_raw, f_raw = gates[..., 0], gates[..., 1] + 3.0  # forget-gate bias init
    if state is None:
        state = {
            "C": jnp.zeros((b, h, dqk, dh), jnp.float32),
            "n": jnp.zeros((b, h, dqk), jnp.float32),
            "m": jnp.full((b, h), -1e30, jnp.float32),
        }
    core = _mlstm_step if step else _mlstm_chunkwise
    hcell, state = core(q, k, v, i_raw, f_raw, state)
    hcell = C.rmsnorm(hcell.reshape(b, s, d_inner), lp["gn"], cfg.norm_eps)
    out = C.linear(lp["down"], hcell * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype))
    return x + out, state, conv_state


# ---------------------------------------------------------------------------
# sLSTM core (sequential scan)
# ---------------------------------------------------------------------------


def _slstm_cell(lp, x, cfg, state=None, step=False):
    """x: (B, S, D). Scalar-memory LSTM with exp gating + block-diag recurrence."""
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h
    pre_all = C.linear(lp["w"], x).astype(jnp.float32)  # (B,S,4D)
    r = lp["r"].astype(jnp.float32)  # (H, dh, 4dh)
    if state is None:
        state = {
            "h": jnp.zeros((b, d), jnp.float32),
            "c": jnp.zeros((b, d), jnp.float32),
            "n": jnp.ones((b, d), jnp.float32),
            "m": jnp.zeros((b, d), jnp.float32),
        }

    def cell(st, pre_t):
        hp = st["h"].reshape(b, h, dh)
        rec = jnp.einsum("bhd,hde->bhe", hp, r).reshape(b, 4 * d)
        # interleave: pre_t (B,4D) ordered [i,f,z,o] along last dim blocks of D
        pre = pre_t + rec.reshape(b, 4, d).reshape(b, 4 * d)
        i_t, f_t, z_t, o_t = jnp.split(pre, 4, axis=-1)
        m_new = jnp.maximum(jax.nn.log_sigmoid(f_t) + st["m"], i_t)
        i_st = jnp.exp(i_t - m_new)
        f_st = jnp.exp(jax.nn.log_sigmoid(f_t) + st["m"] - m_new)
        c_new = f_st * st["c"] + i_st * jnp.tanh(z_t)
        n_new = f_st * st["n"] + i_st
        h_new = jax.nn.sigmoid(o_t) * c_new / jnp.maximum(n_new, 1e-6)
        return {"h": h_new, "c": c_new, "n": n_new, "m": m_new}, h_new

    if step:
        state, h_out = cell(state, pre_all[:, 0])
        return h_out[:, None].astype(x.dtype), state
    # rec applies per step: recurrent weights make this sequential
    pre_seq = pre_all.transpose(1, 0, 2).reshape(s, b, 4, d).reshape(s, b, 4 * d)
    state, hs = jax.lax.scan(cell, state, pre_seq)
    return hs.transpose(1, 0, 2).astype(x.dtype), state


def _slstm_cell_sharded(lp, x, cfg):
    """Train-path sLSTM under shard_map over the batch (dp) axes.

    Without this, autodiff of the time scan places the recurrent-weight
    gradient all-reduce INSIDE the per-timestep loop (measured 412 GB/device
    of collectives at 4k seq — §Perf cell A iteration 3); shard_map keeps the
    recurrence batch-local and psums parameter gradients once at the exit."""
    from jax.sharding import PartitionSpec as P

    from repro.models.context import get_mesh_context

    ctx = get_mesh_context()
    if ctx.mesh is None or not ctx.dp_axes or x.shape[0] % ctx.mesh.shape[ctx.dp_axes[0]] != 0:
        return _slstm_cell(lp, x, cfg)[0]
    dp = tuple(ctx.dp_axes)

    def body(lp_, x_):
        return _slstm_cell(lp_, x_, cfg)[0]

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(jax.tree.map(lambda _: P(), lp), P(dp, None, None)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(lp, x)


def _slstm_block(lp, x, cfg, state=None, step=False):
    hin = C.rmsnorm(x, lp["ln"], cfg.norm_eps)
    if not step and state is None:
        hcell = _slstm_cell_sharded(lp, hin, cfg)
    else:
        hcell, state = _slstm_cell(lp, hin, cfg, state, step)
    hcell = C.rmsnorm(hcell, lp["gn"], cfg.norm_eps)
    x = x + hcell
    x = x + C.mlp_apply(lp["ffn"], C.rmsnorm(x, lp["ln2"], cfg.norm_eps))
    return x, state


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _trunk(params, cfg: ModelConfig, x, pad_to_chunk=True):
    """Run all blocks (training/prefill, fresh state). Returns hidden."""
    b, s, d = x.shape
    chunk = _pick_chunk(max(s, CHUNK_MIN))
    pad = (-s) % (CHUNK_MIN if s < CHUNK else chunk)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    n_seg, m_per = _segments(cfg)

    def m_body(x, lp):
        out, _, _ = _mlstm_block(lp, x, cfg)
        return out, None

    if cfg.remat:
        m_body = jax.checkpoint(m_body)

    if n_seg == 0:
        x, _ = jax.lax.scan(m_body, x, params["m_layers"])
    else:
        def seg_body(x, seg_params):
            mls, sls = seg_params
            x, _ = jax.lax.scan(m_body, x, mls)
            x, _ = _slstm_block(sls, x, cfg)
            return x, None

        if cfg.remat:
            seg_body = jax.checkpoint(seg_body)
        x, _ = jax.lax.scan(seg_body, x, (params["m_layers"], params["s_layers"]))
    return x[:, :s]


def forward(params, cfg: ModelConfig, tokens):
    x = C.embed_lookup(params["embed"], tokens)
    x = _trunk(params, cfg, x)
    x = C.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return C.linear(params["head"], x)


def loss_fn(params, cfg: ModelConfig, batch):
    x = C.embed_lookup(params["embed"], batch["tokens"])
    h = C.rmsnorm(_trunk(params, cfg, x), params["ln_f"], cfg.norm_eps)
    return C.cross_entropy_chunked(
        h[:, :-1], batch["labels"][:, 1:], lambda xc: C.linear(params["head"], xc)
    )


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, dtype=C.DTYPE):
    """Recurrent state — O(1) in sequence length (the long_500k enabler)."""
    d_inner, h, dh = _dims(cfg)
    n_seg, m_per = _segments(cfg)
    n_m = cfg.n_layers if n_seg == 0 else n_seg * m_per
    mshape = (n_seg, m_per) if n_seg else (n_m,)
    dqk = _qk_dim(cfg)
    st = {
        "mC": jnp.zeros((*mshape, batch, h, dqk, dh), jnp.float32),
        "mn": jnp.zeros((*mshape, batch, h, dqk), jnp.float32),
        "mm": jnp.full((*mshape, batch, h), -1e30, jnp.float32),
        "conv": jnp.zeros((*mshape, batch, 3, d_inner), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if n_seg:
        st.update(
            sh=jnp.zeros((n_seg, batch, cfg.d_model), jnp.float32),
            sc=jnp.zeros((n_seg, batch, cfg.d_model), jnp.float32),
            sn=jnp.ones((n_seg, batch, cfg.d_model), jnp.float32),
            sm=jnp.zeros((n_seg, batch, cfg.d_model), jnp.float32),
        )
    return st


def decode_step(params, cfg: ModelConfig, state, tokens):
    """tokens (B,1) single-step decode through the recurrent states."""
    x = C.embed_lookup(params["embed"], tokens)
    n_seg, m_per = _segments(cfg)

    def m_body(x, lp_st):
        lp, Cst, nst, mst, conv = lp_st
        out, new_st, new_conv = _mlstm_block(
            lp, x, cfg, {"C": Cst, "n": nst, "m": mst}, conv, step=True
        )
        return out, (new_st["C"], new_st["n"], new_st["m"], new_conv)

    if n_seg == 0:
        x, (mC, mn, mm, conv) = jax.lax.scan(
            m_body, x, (params["m_layers"], state["mC"], state["mn"], state["mm"], state["conv"])
        )
        new_state = {**state, "mC": mC, "mn": mn, "mm": mm, "conv": conv, "pos": state["pos"] + 1}
    else:
        def seg_body(x, seg):
            mls, mC, mn, mm, conv, sls, sh, sc, sn, sm = seg
            x, (mC, mn, mm, conv) = jax.lax.scan(m_body, x, (mls, mC, mn, mm, conv))
            sst = {"h": sh, "c": sc, "n": sn, "m": sm}
            x, sst = _slstm_block(sls, x, cfg, sst, step=True)
            return x, (mC, mn, mm, conv, sst["h"], sst["c"], sst["n"], sst["m"])

        x, (mC, mn, mm, conv, sh, sc, sn, sm) = jax.lax.scan(
            seg_body, x,
            (params["m_layers"], state["mC"], state["mn"], state["mm"], state["conv"],
             params["s_layers"], state["sh"], state["sc"], state["sn"], state["sm"]),
        )
        new_state = {"mC": mC, "mn": mn, "mm": mm, "conv": conv,
                     "sh": sh, "sc": sc, "sn": sn, "sm": sm, "pos": state["pos"] + 1}
    x = C.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return C.linear(params["head"], x), new_state


# slot (batch) axis per decode-state leaf, negative from the trailing dims —
# broadcast target for the pad-validity mask in bucketed prefill
_B_AXIS = {"mC": -4, "mn": -3, "mm": -2, "conv": -3,
           "sh": -2, "sc": -2, "sn": -2, "sm": -2, "pos": -1}


def prefill(params, cfg: ModelConfig, tokens, state, length=None):
    """Prefill = run the chunkwise trunk, then capture final states by
    replaying the last partial chunk... For simplicity and exactness we run
    the sequence through decode_step via scan when capturing state is needed;
    the serving path uses prefill for logits and decode for continuation.

    ``length`` (B,) marks the real prompt length under bucket padding: logits
    come from position length-1 and recurrent-state updates are gated off for
    pad steps (the state is not page-addressable, so pads must not touch it)."""
    # chunkwise trunk for logits; state capture via per-chunk final states
    x = C.embed_lookup(params["embed"], tokens)
    h = _trunk(params, cfg, x)
    h = C.rmsnorm(C.select_at_length(h, length), params["ln_f"], cfg.norm_eps)
    logits = C.linear(params["head"], h)

    def step(st, t_i):
        t, i = t_i
        lg, new = decode_step(params, cfg, st, t[:, None])
        if length is not None:
            valid = i < jnp.asarray(length, jnp.int32).reshape(-1)
            new = C.gate_state_update(new, st, valid, _B_AXIS)
        return new, ()

    s = tokens.shape[1]
    state, _ = jax.lax.scan(step, state, (tokens.T, jnp.arange(s)))
    return logits, state


def count_params(cfg: ModelConfig):
    d = cfg.d_model
    d_inner, h, dh = _dims(cfg)
    dqk = _qk_dim(cfg)
    m_layer = (d * 2 * d_inner + 4 * d_inner + 2 * d_inner * dqk * h
               + d_inner * d_inner + d_inner * 2 * h + d_inner * d + 2 * d_inner + d)
    s_layer = 4 * d * d + h * (d // h) * 4 * (d // h) + 3 * d * 2 * d + 4 * d
    n_seg, m_per = _segments(cfg)
    n_m = cfg.n_layers if n_seg == 0 else n_seg * m_per
    n_s = 0 if n_seg == 0 else n_seg
    total = n_m * m_layer + n_s * s_layer + cfg.padded_vocab * d * 2 + d
    return total, total
