"""Everything the benchmark takes from the program, in one place: the model
configuration, the packer of one W4A4 linear, and the paged ragged serving
engine.

An architecture (``bench/architectures/<name>.py``) draws its weights on the
device and packs each linear with ``pack_linear`` (the program's own
``pack_twinquant_weights``) into the leaves that ``quantize_params`` emits,
layer by layer inside one jitted call, so the float linears of the full
model never exist at once. The engine's own ``fuse_params`` then merges
sibling packs as in serving.
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


def pack_linear(lin: dict, a_bits: int, group: int) -> dict:
    """One drawn linear (``model.draw_linear``) as the program's W4A4 pack."""
    from repro.kernels.ref import pack_twinquant_weights

    k = lin["r"].shape[0]
    tq = pack_twinquant_weights(
        M.dequant(lin["u"], lin["us"]), M.dequant(lin["v"], lin["vs"]),
        M.dequant(lin["r"], lin["rs"]), a_bits=a_bits, group=min(group, k),
    )
    out = {"up": tq.up, "us": tq.us, "vp": tq.vp, "vs": tq.vs, "rp": tq.rp,
           "rs": tq.rs, "abits": jnp.zeros((a_bits,), jnp.int8)}
    if "b" in lin:
        out["b"] = lin["b"]
    return out


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
