"""Everything the benchmark takes from the program, in one place: the model
configuration, the packed W4A4 tree built from the seeded model, and the
paged ragged serving engine.

The weights are drawn on the device by ``harness/model.py`` and packed with
the program's own ``pack_twinquant_weights`` into the leaves that
``quantize_params`` emits, layer by layer inside one jitted call, so the
float linears of the full model never exist at once. The engine's own
``fuse_params`` then merges sibling packs as in serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import model as M


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the registered
    name with the file's overrides and its quantization."""
    from repro.configs import QuantSpec, get_config

    q = conf["quant"]
    spec = QuantSpec(mode=q["mode"], rank=q["rank"], group_size=q["group_size"])
    return get_config(conf["registered"], **conf.get("overrides", {})).replace(quant=spec)


def arch_of(cfg) -> M.Arch:
    return M.Arch(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        vocab=cfg.vocab, padded_vocab=cfg.padded_vocab, qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta, rope_fraction=cfg.rope_fraction,
        norm_eps=cfg.norm_eps, rank=cfg.quant.rank, group=cfg.quant.group_size,
        a_bits=cfg.quant.a_bits,
    )


def _pack_linear(lin: dict, arch: M.Arch) -> dict:
    from repro.kernels.ref import pack_twinquant_weights

    k = lin["r"].shape[0]
    tq = pack_twinquant_weights(
        M.dequant(lin["u"], lin["us"]), M.dequant(lin["v"], lin["vs"]),
        M.dequant(lin["r"], lin["rs"]), a_bits=arch.a_bits, group=min(arch.group, k),
    )
    out = {"up": tq.up, "us": tq.us, "vp": tq.vp, "vs": tq.vs, "rp": tq.rp,
           "rs": tq.rs, "abits": jnp.zeros((arch.a_bits,), jnp.int8)}
    if "b" in lin:
        out["b"] = lin["b"]
    return out


def _packed_layer(lkey, arch: M.Arch, layer) -> dict:
    w = M.draw_layer(lkey, arch, layer)
    tree = {"attn": {}, "mlp": {}, "ln1": w["ln1"], "ln2": w["ln2"]}
    for name, (parent, *_) in arch.linears().items():
        tree[parent][name] = _pack_linear(w[name], arch)
    return tree


def build_params(cfg, seed: int) -> dict:
    """The unfused packed W4A4 tree of ``cfg`` from ``seed``, on the default
    device, in one jitted call (layers drawn and packed one at a time)."""
    arch = arch_of(cfg)

    @jax.jit
    def build(key):
        layers = jax.lax.map(
            lambda i: _packed_layer(M.layer_key(key, i), arch, i),
            jnp.arange(arch.n_layers),
        )
        return {"embed": M.draw_embed(key, arch), "layers": layers,
                "ln_f": M.draw_final_norm(key, arch)}

    return build(M.root_key(seed))


def fuse(params, device):
    """The program's own ``fuse_params`` (sibling packs merged as in
    serving), on ``device``."""
    from repro.core.twinquant import fuse_params

    return jax.device_put(fuse_params(params), device)


def build_engine(cfg, params, traffic: dict, n_pages: int):
    """The paged ragged engine the window drives, from fused ``params``
    (the constructor's own ``fuse_params`` is then a no-op), on the default
    device."""
    from repro.launch.serve import ContinuousBatchingEngine

    eng_conf = traffic["engine"]
    return ContinuousBatchingEngine(
        cfg, params, batch_slots=eng_conf["batch_slots"],
        max_len=eng_conf["max_len"], paged=True, page_size=eng_conf["page_size"],
        n_pages=n_pages, prefix_caching=eng_conf["prefix_caching"],
        ragged=True, token_budget=eng_conf["token_budget"],
        on_truncation="reject",
    )
