"""The seeded W4A4 model of the dense GQA architecture
(``bench/architectures/dense_gqa.py``): the packed tree has the layout
quantize_params emits, the packs hold exactly the drawn codes, and the seed
decides it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib as T
from harness import model as M

from repro.configs import QuantSpec, get_config
from repro.core.twinquant import fuse_params, quantize_params
from repro.kernels.ref import unpack_rows_groupsplit
from repro.models.registry import get_model

A = T.architecture("dense_gqa")


def small(registered):
    # contraction widths are multiples of 256, so every linear is eligible
    # for packing, as at the published widths
    return get_config(registered).replace(quant=QuantSpec(mode="w4a4"), **T.TINY)


def layout(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), tree)


@pytest.mark.parametrize("registered", ["phi4-mini-3.8b", "qwen2-1.5b"])
def test_seeded_tree_has_quantize_params_layout(registered):
    cfg = small(registered)
    ours = A.build_params(A.arch_of(cfg), 5)

    def quantized():
        params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
        return quantize_params(params, cfg, cfg.quant)

    theirs = jax.eval_shape(quantized)
    assert layout(ours) == layout(theirs)
    assert layout(fuse_params(ours)) == layout(jax.eval_shape(lambda: fuse_params(quantized())))
    assert "qkv" in fuse_params(ours)["layers"]["attn"]


def test_packs_hold_the_drawn_codes():
    cfg = small("qwen2-1.5b")
    arch = A.arch_of(cfg)
    packed = A.build_params(arch, 9)
    drawn = A.draw_layer(M.layer_key(M.root_key(9), 1), arch, 1)
    for parent, name in (("attn", "q"), ("attn", "o"), ("mlp", "down")):
        pk = jax.tree_util.tree_map(lambda a: a[1], packed["layers"][parent][name])
        lin = drawn[name]
        for codes, scales, p, s, g in (("r", "rs", "rp", "rs", 128), ("u", "us", "up", "us", 128),
                                       ("v", "vs", "vp", "vs", 128)):
            np.testing.assert_array_equal(unpack_rows_groupsplit(pk[p], g), lin[codes])
            np.testing.assert_allclose(pk[s], lin[scales], rtol=1e-6)
    assert packed["layers"]["attn"]["q"]["b"].dtype == jnp.bfloat16


def test_the_seed_decides_the_weights():
    arch = A.arch_of(small("phi4-mini-3.8b"))
    a, b = A.build_params(arch, 2**33 + 1), A.build_params(arch, 2**33 + 1)
    c, d = A.build_params(arch, 1), A.build_params(arch, 2)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    for other in (c, d):  # 2**33 + 1 differs from its low 32 bits, and 1 from 2
        assert not bool((a["embed"] == other["embed"]).all())
        up, other_up = a["layers"]["mlp"]["up"]["rp"], other["layers"]["mlp"]["up"]["rp"]
        assert not bool((up == other_up).all())


def test_dual_component_weight_keeps_the_variance():
    # W = R + U V has about the variance 1 / K per entry, so x W keeps the
    # scale of x through the full depth
    arch = A.arch_of(small("qwen2-1.5b"))
    lin = M.draw_linear(jax.random.PRNGKey(0), 1024, 1024, arch.rank, arch.group, False)
    lowrank = M.dequant(lin["u"], lin["us"]) @ M.dequant(lin["v"], lin["vs"])
    w = M.dequant(lin["r"], lin["rs"]) + lowrank
    assert 0.8 < float(jnp.std(w)) * 1024**0.5 < 1.25
