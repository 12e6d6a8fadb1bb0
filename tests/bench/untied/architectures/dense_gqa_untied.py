"""Dense pre-norm GQA decoder whose lm head is a table of its own (untied),
the first structural step from ``dense_gqa`` towards a model such as
Trinity-Mini. Everything else is ``dense_gqa``'s, drawn from the same keys;
the head is drawn from key 4 as a bf16 (D, padded_vocab) linear that the
program keeps unquantized (``params["head"]["w"]``).

The benchmark's tests add this file to a checkout to show that a new
architecture is new files only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import load, model as M

D = load.sibling(__file__, "architectures", "dense_gqa")

HEAD_STD = 0.02

Arch = D.Arch
launch_cost = D.launch_cost  # the head costs as tied: its table once
kv_bytes_per_token = D.kv_bytes_per_token


def arch_of(cfg) -> D.Arch:
    if cfg.tie_embeddings:
        raise ValueError("dense_gqa_untied serves an untied lm head: set tie_embeddings false")
    return D.arch_of(cfg)


def draw_head(seed_key, arch: D.Arch) -> jax.Array:
    """The lm head (D, padded_vocab) bf16."""
    k = jax.random.fold_in(seed_key, 4)
    return (jax.random.normal(k, (arch.d_model, arch.padded_vocab)) * HEAD_STD).astype(
        jnp.bfloat16
    )


def build_params(arch: D.Arch, seed: int) -> dict:
    """``dense_gqa``'s tree and the head, in one jitted call."""

    @jax.jit
    def build(key):
        layers = jax.lax.map(lambda i: D.packed_layer(M.layer_key(key, i), arch, i),
                             jnp.arange(arch.n_layers))
        return {"embed": D.draw_embed(key, arch), "layers": layers,
                "ln_f": D.draw_final_norm(key, arch), "head": {"w": draw_head(key, arch)}}

    return build(M.root_key(seed))
