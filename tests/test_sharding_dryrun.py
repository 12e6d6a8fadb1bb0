"""Sharding + dry-run machinery tests on a small forced-device mesh.

These exercise the exact code paths the 512-device production dry-run uses
(param specs, batch specs, decode-state specs, lower+compile with shardings,
HLO cost model) at 4-device scale so they run in CI time.
"""

import os

import pytest

# must precede jax import in this test process
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.hlo_cost import analyze_hlo  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.launch.sharding import (  # noqa: E402
    batch_specs,
    decode_state_specs,
    make_shardings,
    param_specs,
)
from repro.launch.train import make_train_step  # noqa: E402
from repro.models.context import MeshContext, set_mesh_context  # noqa: E402
from repro.models.registry import get_model  # noqa: E402
from repro.optim import AdamW  # noqa: E402


@pytest.fixture()
def mesh_ctx():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 forced host devices")
    mesh = make_debug_mesh((2, 2))
    ctx = MeshContext(mesh=mesh, dp_axes=("data",), tp_axis="model",
                      ep_axis="model", fsdp_axes=("data",))
    set_mesh_context(ctx)
    yield mesh, ctx
    set_mesh_context(MeshContext())


def _params_sds(cfg, model):
    return jax.eval_shape(lambda k: model.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "olmoe-1b-7b", "zamba2-1.2b"])
def test_param_specs_divide(mesh_ctx, arch):
    """Specs must map every leaf and only use axis sizes that divide dims."""
    mesh, ctx = mesh_ctx
    cfg = get_config(arch, reduced=True)
    model = get_model(cfg)
    sds = _params_sds(cfg, model)
    specs = param_specs(cfg, sds, ctx)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree.leaves(sds)
    assert len(flat_s) == len(flat_p)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (leaf.shape, spec)


def test_train_step_compiles_sharded(mesh_ctx):
    mesh, ctx = mesh_ctx
    cfg = get_config("qwen2-1.5b", reduced=True).replace(d_model=256, d_ff=512, vocab=512)
    model = get_model(cfg)
    sds = _params_sds(cfg, model)
    pspecs = param_specs(cfg, sds, ctx)
    pshard = make_shardings(mesh, pspecs)
    opt = AdamW()
    osds = jax.eval_shape(opt.init, sds)
    oshard = make_shardings(mesh, type(osds)(mu=pspecs, nu=pspecs, count=P()))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
    bshard = make_shardings(mesh, batch_specs(cfg, batch, ctx))
    step = make_train_step(cfg, opt)
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            step, in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
        ).lower(sds, osds, batch).compile()
    r = analyze_hlo(compiled.as_text())
    assert r["flops"] > 0
    assert r["coll_bytes"] > 0  # DP gradient reduction must be present


def test_decode_state_specs_long_context(mesh_ctx):
    """long_500k rule: batch=1 can't use dp -> sequence dim is sharded."""
    mesh, ctx = mesh_ctx
    cfg = get_config("zamba2-1.2b", reduced=True)
    model = get_model(cfg)
    state = jax.eval_shape(lambda: model.init_decode_state(cfg, 1, 4096))
    specs = decode_state_specs(cfg, state, ctx, seq_shard=True)
    k_spec = specs["shared_k"]
    assert any(a is not None for a in tuple(k_spec)), k_spec
    # the seq dim (index 2) carries the sharding
    assert tuple(k_spec)[2] is not None


def test_ep_moe_collectives_present(mesh_ctx):
    """The EP path must lower to all-to-all over the expert axis."""
    mesh, ctx = mesh_ctx
    cfg = get_config("olmoe-1b-7b", reduced=True).replace(remat=False)
    model = get_model(cfg)
    sds = _params_sds(cfg, model)
    pshard = make_shardings(mesh, param_specs(cfg, sds, ctx))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
    bshard = make_shardings(mesh, batch_specs(cfg, batch, ctx))
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            lambda p, b: model.loss_fn(p, cfg, b),
            in_shardings=(pshard, bshard),
        ).lower(sds, batch).compile()
    r = analyze_hlo(compiled.as_text())
    assert r["coll_detail"].get("all-to-all", 0) > 0, r["coll_detail"]


def test_hlo_cost_scan_multiplier():
    """The cost model must multiply scan bodies by trip count."""
    def scan_fn(w, x):
        def body(x, wl):
            return x @ wl, None
        y, _ = jax.lax.scan(body, x, w)
        return jnp.sum(y)

    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    compiled = jax.jit(scan_fn).lower(w, x).compile()
    r = analyze_hlo(compiled.as_text())
    expect = 8 * 2 * 16 * 128 * 128
    assert 0.8 * expect < r["flops"] < 2.0 * expect, (r["flops"], expect)
