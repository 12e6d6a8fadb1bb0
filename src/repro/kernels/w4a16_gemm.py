"""Weight-only int4 GEMM (W4A16) — the AWQ/GPTQ-style baseline system.

Same packing/tiling conventions as the dual-component kernel, but activations
stay bf16: packed int4 weights are sign-extended and dequantized to bf16 in
VMEM, then dotted on the MXU with f32 accumulation. Serves as (a) the W4A16
baseline the paper compares against and (b) the fallback path for layers
whose shapes don't admit full W4A4 (e.g. tiny ranks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contracts import validate_w4a16

__all__ = ["w4a16_gemm"]


def _unpack_rows(p: jax.Array) -> jax.Array:
    p32 = p.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(p32, 24), 28)
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)


def _w4a16_kernel(x_ref, wp_ref, ws_ref, o_ref, acc_s, *, bk: int, G: int, n_k: int):
    k = pl.program_id(2)
    gpb = bk // G

    @pl.when(k == 0)
    def _zero():
        acc_s[...] = jnp.zeros_like(acc_s)

    xb = x_ref[...].astype(jnp.bfloat16)
    for g in range(gpb):
        wg = _unpack_rows(wp_ref[g * (G // 2) : (g + 1) * (G // 2), :])  # (G, bn)
        sg = ws_ref[pl.ds(k * gpb + g, 1), :]  # (1, bn)
        w_deq = (wg.astype(jnp.float32) * sg).astype(jnp.bfloat16)
        xg = xb[:, g * G : (g + 1) * G]
        acc_s[...] += jax.lax.dot_general(
            xg, w_deq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = acc_s[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "block_m", "block_n", "block_k", "interpret"))
def w4a16_gemm(
    x: jax.Array,
    wp: jax.Array,
    ws: jax.Array,
    *,
    group: int = 128,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) bf16; wp: (K/2, N) packed int4; ws: (K/G, N) f32 -> (M, N) bf16."""
    m, k = x.shape
    n = wp.shape[1]
    # grid-coverage/divisibility + VMEM-budget contracts (raise ContractError
    # with the violated relation before Mosaic sees the launch)
    validate_w4a16(m, n, k, group, block_m, block_n, block_k)
    n_k = k // block_k

    kernel = functools.partial(_w4a16_kernel, bk=block_k, G=group, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k // 2, block_n), lambda mi, ni, ki: (ki, ni)),
            # whole-K scale strip: the full K/G extent keeps the block legal
            # for any block_k (a (block_k/G, block_n) block breaks the (8, 128)
            # rule below block_k = 8 * group)
            pl.BlockSpec((k // group, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY, pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="w4a16_gemm",
    )(x, wp, ws)
