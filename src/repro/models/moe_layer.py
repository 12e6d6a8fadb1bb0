"""Mixture-of-Experts layer: top-k routing, sort-based capacity dispatch,
and expert parallelism via shard_map + all_to_all over the EP mesh axis.

Two execution paths, numerically equivalent when the mesh is trivial:

* **local** (no mesh context): all experts resident, sort-based dispatch,
  no collectives — used by CPU smoke tests and single-device examples.
* **EP** (mesh context set): tokens sharded over (dp_axes + ep_axis), expert
  weights sharded over ep_axis; each shard routes its local tokens, packs
  per-expert capacity buffers, exchanges them with a single
  ``jax.lax.all_to_all`` (the jax-native analogue of the NCCL a2a the GPU
  systems use), runs its resident experts, and reverses the exchange.
  FSDP'd expert weights are all-gathered over the fsdp axes inside the shard
  (ZeRO-3 semantics, explicit).

The einsum-one-hot GShard formulation is deliberately NOT used: at
DeepSeek-V3 scale its dispatch tensor is O(T·E·C) and its einsum FLOPs exceed
the expert FLOPs by orders of magnitude (see DESIGN.md §5).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ModelConfig
from repro.models import common as C
from repro.models.context import get_mesh_context


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    f = cfg.d_ff_expert
    e = cfg.n_experts
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    std = 1.0 / (d**0.5)
    p = {
        "router": (jax.random.normal(kr, (d, e)) * std).astype(jnp.float32),
        "gate": {"w": (jax.random.normal(kg, (e, d, f)) * std).astype(C.DTYPE)},
        "up": {"w": (jax.random.normal(ku, (e, d, f)) * std).astype(C.DTYPE)},
        "down": {"w": (jax.random.normal(kd, (e, f, d)) * (1.0 / f**0.5)).astype(C.DTYPE)},
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = C.mlp_init(ks, d, fs)
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(router_w: jax.Array, x: jax.Array, cfg: ModelConfig):
    """x (T, D) -> (top_w (T,k) f32, top_i (T,k) i32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # GShard/Switch load-balancing aux loss
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    one_hot = jax.nn.one_hot(top_i[:, 0], e)  # fraction by top-1 assignment
    fe = jnp.mean(one_hot, axis=0)
    aux = e * jnp.sum(fe * me)
    return top_w, top_i.astype(jnp.int32), aux


def _dispatch_indices(top_i: jax.Array, k: int, E: int, C: int):
    """Sort-based capacity assignment. Returns (slot (T*k,), tok (T*k,), keep)."""
    fe = top_i.reshape(-1)  # (T*k,)
    order = jnp.argsort(fe, stable=True)
    fe_s = fe[order]
    tok_s = order // k
    counts = jnp.bincount(fe_s, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(fe_s.shape[0], dtype=jnp.int32) - starts[fe_s].astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, fe_s * C + pos, E * C)  # overflow -> scratch row
    return slot, tok_s, order, keep


_EXPERT_KEYS = ("gate", "up", "gate_up", "down")


def _expert_ffn(p: dict, xb: jax.Array) -> jax.Array:
    """xb (E_loc, Cap, D) -> (E_loc, Cap, D); bf16 or quantized experts."""
    if "gate" in p and "w" in p["gate"]:
        g = jnp.einsum("ecd,edf->ecf", xb, p["gate"]["w"].astype(xb.dtype))
        u = jnp.einsum("ecd,edf->ecf", xb, p["up"]["w"].astype(xb.dtype))
        h = C.swiglu(g, u)
        return jnp.einsum("ecf,efd->ecd", h, p["down"]["w"].astype(xb.dtype))
    # quantized experts: vmap the linear dispatcher over the expert dim;
    # gate/up share the expert input, so they run as one fused launch
    def one(pe, xe):
        g, u = C.linear_group(pe, ("gate", "up"), "gate_up", xe)
        return C.linear(pe["down"], C.swiglu(g, u))

    return jax.vmap(one)(p, xb)


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, (c + 7) // 8 * 8)


# ---------------------------------------------------------------------------
# local (collective-free) path
# ---------------------------------------------------------------------------


def _moe_local(p: dict, x: jax.Array, cfg: ModelConfig):
    t, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    Cp = _capacity(t, cfg)
    top_w, top_i, aux = route(p["router"], x, cfg)
    slot, tok_s, order, keep = _dispatch_indices(top_i, k, E, Cp)
    buf = jnp.zeros((E * Cp + 1, d), x.dtype).at[slot].set(x[tok_s])
    experts = {kk: p[kk] for kk in _EXPERT_KEYS if kk in p}
    yb = _expert_ffn(experts, buf[: E * Cp].reshape(E, Cp, d))
    yb = jnp.concatenate([yb.reshape(E * Cp, d), jnp.zeros((1, d), x.dtype)], axis=0)
    w_s = top_w.reshape(-1)[order].astype(x.dtype)
    contrib = yb[slot] * (w_s * keep.astype(x.dtype))[:, None]
    out = jnp.zeros((t, d), x.dtype).at[tok_s].add(contrib)
    return out, aux


# ---------------------------------------------------------------------------
# expert-parallel path (shard_map over the mesh)
# ---------------------------------------------------------------------------


def _moe_shard_body(x, router_w, gate, up, down, *, cfg: ModelConfig, ep_axis: str,
                    ep_size: int, fsdp_axes: tuple[str, ...], all_axes: tuple[str, ...]):
    """Per-shard body. x: (T_loc, D); experts: (E_loc, ...) local slices."""
    ep = ep_size  # static mesh extent (jax.lax.axis_size is newer-jax-only)
    for ax in fsdp_axes:  # ZeRO-3: gather the fsdp-sharded expert dims
        gate = jax.lax.all_gather(gate, ax, axis=1, tiled=True)
        up = jax.lax.all_gather(up, ax, axis=1, tiled=True)
        down = jax.lax.all_gather(down, ax, axis=2, tiled=True)
    t, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    e_loc = E // ep
    Cp = _capacity(t, cfg)

    top_w, top_i, aux = route(router_w, x, cfg)
    slot, tok_s, order, keep = _dispatch_indices(top_i, k, E, Cp)
    buf = jnp.zeros((E * Cp + 1, d), x.dtype).at[slot].set(x[tok_s])
    buf = buf[: E * Cp].reshape(ep, e_loc, Cp, d)
    # exchange: shard i sends its buffer slice for shard j's experts to j
    buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    xin = buf.transpose(1, 0, 2, 3).reshape(e_loc, ep * Cp, d)
    y = _expert_ffn({"gate": {"w": gate}, "up": {"w": up}, "down": {"w": down}}, xin)
    y = y.reshape(e_loc, ep, Cp, d).transpose(1, 0, 2, 3)
    y = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    yb = jnp.concatenate([y.reshape(E * Cp, d), jnp.zeros((1, d), x.dtype)], axis=0)
    w_s = top_w.reshape(-1)[order].astype(x.dtype)
    contrib = yb[slot] * (w_s * keep.astype(x.dtype))[:, None]
    out = jnp.zeros((t, d), x.dtype).at[tok_s].add(contrib)
    return out, jax.lax.pmean(aux, all_axes)[None]


def _token_axes_for(ctx, t: int) -> tuple[str, ...]:
    """Largest prefix of (dp + ep) axes whose product divides the token count
    (decode steps may have fewer tokens than devices)."""
    axes = []
    prod = 1
    for ax in ctx.token_axes:
        size = ctx.mesh.shape[ax]
        if t % (prod * size) != 0:
            break
        axes.append(ax)
        prod *= size
    return tuple(axes)


def _moe_ep(p: dict, x: jax.Array, cfg: ModelConfig):
    ctx = get_mesh_context()
    mesh = ctx.mesh
    tok_axes = _token_axes_for(ctx, x.shape[0])
    ep_axis = ctx.ep_axis
    fsdp = tuple(ax for ax in ctx.fsdp_axes if ax != ep_axis)
    fs = fsdp if fsdp else None

    def body(xx, rw, g, u, dn):
        return _moe_shard_body(
            xx, rw, g, u, dn, cfg=cfg, ep_axis=ep_axis, ep_size=mesh.shape[ep_axis],
            fsdp_axes=fsdp, all_axes=tok_axes or (ep_axis,)
        )
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(tok_axes, None),
            P(None, None),
            P(ep_axis, fs, None),  # gate (E, D, F): E over ep, D over fsdp
            P(ep_axis, fs, None),  # up
            P(ep_axis, None, fs),  # down (E, F, D): D over fsdp
        ),
        out_specs=(P(tok_axes, None), P(None)),
        check_vma=False,
    )(x, p["router"], p["gate"]["w"], p["up"]["w"], p["down"]["w"])
    return out, jnp.mean(aux)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig):
    """x (B, S, D) -> (B, S, D), aux_loss. Chooses EP vs local path by mesh
    context; adds the shared-expert branch (DeepSeek-style) if present."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    ctx = get_mesh_context()
    use_ep = (
        ctx.mesh is not None
        and ctx.ep_size > 1
        and cfg.n_experts % ctx.ep_size == 0
        # EP shard_map path is bf16-experts only (for now)
        and "gate" in p and "w" in p["gate"]
    )
    if use_ep:
        out, aux = _moe_ep(p, xf, cfg)
    else:
        out, aux = _moe_local(p, xf, cfg)
    if "shared" in p:
        out = out + C.mlp_apply(p["shared"], xf)
    return out.reshape(b, s, d), aux
