"""The serving kernels compile for one TPU v5e at LLaMA3-8B widths.

Interpret mode does not enforce the chip's tiling rules, so the kernel
agreement tests elsewhere cannot show that Mosaic accepts a kernel. These
tests hand each serving kernel shapes carrying a described (not attached)
v5e device and compile it with the TPU compiler that ships with jaxlib:
what the chip's compiler would refuse fails here, at no chip time. Nothing
runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture and nowhere else:
only one process at a time may load the TPU library, so describing it while
a module is imported would make parallel test workers collect different
tests. Keep every such compile in this one file.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import QuantSpec, get_config
from repro.kernels.autotune import DECODE_M_MAX, heuristic_blocks
from repro.kernels.paged_attention import paged_decode_kernel
from repro.kernels.ragged_attention import ragged_attention_kernel
from repro.kernels.ref import TwinQuantGroupWeights, TwinQuantWeights
from repro.kernels.twinquant_dual_gemm import dual_gemm, dual_gemm_group
from repro.kernels.twinquant_dual_gemv import dual_gemv, dual_gemv_group
from repro.kernels.w4a16_gemm import w4a16_gemm

CFG = get_config("llama3-8b")
SPEC = QuantSpec(mode="w4a4")
D, F = CFG.d_model, CFG.d_ff
KVD = CFG.n_kv_heads * CFG.head_dim
T = 256  # prefill rows: the ragged engine's token budget
PAGE = 16
SLOTS = DECODE_M_MAX
MAXP = 24  # pages per slot

# fused groups as fuse_params builds them: (K, segment widths)
GROUPS = {"qkv": (D, (D, KVD, KVD)), "gate_up": (D, (F, F))}
SINGLES = {"o": (D, D), "down": (F, D)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; it must hold a Mosaic kernel."""
    sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes
    )
    text = jax.jit(fn).lower(*sds).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel was not lowered to Mosaic"


S = jax.ShapeDtypeStruct
G, R = SPEC.group_size, SPEC.rank
RG = min(G, R)


def _pack(k, n):
    """Shapes of one dual-component pack, in TwinQuantWeights field order."""
    return (S((k // 2, R), jnp.int8), S((k // G, R), jnp.float32),
            S((R // 2, n), jnp.int8), S((R // RG, n), jnp.float32),
            S((k // 2, n), jnp.int8), S((k // G, n), jnp.float32))


def _single(*arrays):
    return TwinQuantWeights(*arrays, group=G, rgroup=RG, a_bits=SPEC.a_bits)


def _group_pack(k, seg_n):
    """Shapes of a fused pack, in TwinQuantGroupWeights field order."""
    return (S((k // 2, R * len(seg_n)), jnp.int8), S((k // G, R * len(seg_n)), jnp.float32),
            tuple(S((R // 2, n), jnp.int8) for n in seg_n),
            tuple(S((R // RG, n), jnp.float32) for n in seg_n),
            S((k // 2, sum(seg_n)), jnp.int8), S((k // G, sum(seg_n)), jnp.float32))


def _group(up, us, vps, vss, rp, rs):
    return TwinQuantGroupWeights(up, us, tuple(vps), tuple(vss), rp, rs, group=G,
                                 rgroups=(RG,) * len(vps), a_bits=SPEC.a_bits)


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_dual_gemv_group_compiles(one_chip, name):
    k, seg_n = GROUPS[name]
    _, bn, _ = heuristic_blocks("dual_decode_fused", SLOTS, math.gcd(*seg_n), k, G, R)
    _compile(one_chip, lambda x, *w: dual_gemv_group(x, _group(*w), block_n=bn),
             S((SLOTS, k), jnp.bfloat16), *_group_pack(k, seg_n))


@pytest.mark.parametrize("block_k", [None, 512])
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_dual_gemm_group_compiles(one_chip, name, block_k):
    """``None`` takes the router's blocks; 512 puts four scale groups in a K
    block, which a per-K-block scale BlockSpec could not tile."""
    k, seg_n = GROUPS[name]
    bm, bn, bk = heuristic_blocks("dual_prefill_fused", T, math.gcd(*seg_n), k, G, R)
    _compile(one_chip, lambda x, *w: dual_gemm_group(
        x, _group(*w), block_m=bm, block_n=bn, block_k=block_k or bk),
        S((T, k), jnp.bfloat16), *_group_pack(k, seg_n))


@pytest.mark.parametrize("name", sorted(SINGLES))
def test_dual_single_pack_compiles(one_chip, name):
    k, n = SINGLES[name]
    _, bn, _ = heuristic_blocks("dual_decode", SLOTS, n, k, G, R)
    _compile(one_chip, lambda x, *w: dual_gemv(x, _single(*w), block_n=bn),
             S((SLOTS, k), jnp.bfloat16), *_pack(k, n))
    bm, bn, bk = heuristic_blocks("dual_prefill", T, n, k, G, R)
    _compile(one_chip, lambda x, *w: dual_gemm(
        x, _single(*w), block_m=bm, block_n=bn, block_k=bk),
        S((T, k), jnp.bfloat16), *_pack(k, n))


@pytest.mark.parametrize("block_k", [512, 1024])
def test_w4a16_gemm_compiles(one_chip, block_k):
    _compile(one_chip, lambda x, wp, ws: w4a16_gemm(
        x, wp, ws, group=G, block_m=128, block_n=512, block_k=block_k),
        S((T, D), jnp.bfloat16), S((D // 2, F), jnp.int8), S((D // G, F), jnp.float32))


def _pools():
    pool = S((SLOTS * MAXP + 1, PAGE, CFG.n_kv_heads, CFG.head_dim), jnp.bfloat16)
    return pool, pool, S((SLOTS, MAXP), jnp.int32)


@pytest.mark.parametrize("commit", [False, True])
@pytest.mark.parametrize("sq", [1, 4])
def test_paged_decode_compiles(one_chip, sq, commit):
    """sq=1 is plain decode, sq=4 a speculative verify step; both are below
    the 8-row sublane tile that a per-slot row block would need."""
    kp, vp, bt = _pools()
    rows = S((SLOTS, sq, CFG.n_kv_heads, CFG.head_dim), jnp.bfloat16)
    _compile(one_chip, lambda *a: paged_decode_kernel(*a, commit=commit),
             S((SLOTS, sq, CFG.n_heads, CFG.head_dim), jnp.bfloat16), kp, vp, rows, rows,
             bt, S((SLOTS,), jnp.int32))


def test_ragged_attention_compiles(one_chip):
    kp, vp, bt = _pools()
    rows = S((T, CFG.n_kv_heads, CFG.head_dim), jnp.bfloat16)
    idx = S((T,), jnp.int32)
    _compile(one_chip, ragged_attention_kernel,
             S((T, CFG.n_heads, CFG.head_dim), jnp.bfloat16), kp, vp, rows, rows, bt,
             idx, idx, S((SLOTS,), jnp.int32))
