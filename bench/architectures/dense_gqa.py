"""Dense pre-norm GQA decoder (Phi-3/Phi-4-mini, Qwen2): every layer holds
attention (q, k, v, o) and a SiLU-gated MLP (gate, up, down), each linear
W4A4; one table serves as embedding and lm head (tied).

A configuration names its architecture (``"architecture"`` in
``bench/configs/<name>.json``); the harness finds this module by that name
and calls the four functions below. The program's configuration, the plain
reference (``references/dense_gqa.py``) and the metric readers see the
``Arch`` that ``arch_of`` returns.

Weights are drawn from the seed with ``harness/model.py``'s pieces, in a
fixed order of ``fold_in`` keys: layer ``i`` from ``model.layer_key(key,
i)``, its linears in ``Arch.linears`` order (keys 0-6), its norms from key
100; the table from key 2, the final norm from key 3.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from harness import costs, model as M, program

EMBED_STD = 0.02
NORM_LOG_STD = 0.1  # norm gains are exp(N(0, 0.1^2))
OUT_LINEARS = ("o", "down")  # the linears that write into the residual stream


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the generator and the reference need, read from the
    program's configuration."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    qkv_bias: bool
    rope_theta: float
    rope_fraction: float
    norm_eps: float
    rank: int
    group: int
    a_bits: int

    def linears(self) -> dict:
        """name -> (parent, K, N, has_bias), in a fixed order."""
        d, h, kv, hd, f = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff
        return {
            "q": ("attn", d, h * hd, self.qkv_bias),
            "k": ("attn", d, kv * hd, self.qkv_bias),
            "v": ("attn", d, kv * hd, self.qkv_bias),
            "o": ("attn", h * hd, d, False),
            "gate": ("mlp", d, f, False),
            "up": ("mlp", d, f, False),
            "down": ("mlp", f, d, False),
        }

    def rank_of(self, k: int, n: int) -> int:
        return M.rank_of(self.rank, k, n)


def arch_of(cfg) -> Arch:
    """The ``Arch`` of the program's ``ModelConfig``."""
    return Arch(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        vocab=cfg.vocab, padded_vocab=cfg.padded_vocab, qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta, rope_fraction=cfg.rope_fraction,
        norm_eps=cfg.norm_eps, rank=cfg.quant.rank, group=cfg.quant.group_size,
        a_bits=cfg.quant.a_bits,
    )


def draw_layer(lkey, arch: Arch, layer) -> dict:
    """Every weight of decoder layer ``layer`` (an int or a traced index):
    {linear name: model.draw_linear(...), "ln1": (D,), "ln2": (D,)} (norm
    gains bf16)."""
    out = {}
    out_gain = jnp.where(layer == 0, 1.0, M.OUT_GAIN)
    for i, (name, (_, k, n, bias)) in enumerate(arch.linears().items()):
        gain = out_gain if name in OUT_LINEARS else 1.0
        out[name] = M.draw_linear(jax.random.fold_in(lkey, i), k, n, arch.rank, arch.group,
                                  bias, gain)
    kn = jax.random.fold_in(lkey, 100)
    k1, k2 = jax.random.split(kn)
    d = arch.d_model
    out["ln1"] = jnp.exp(NORM_LOG_STD * jax.random.normal(k1, (d,))).astype(jnp.bfloat16)
    out["ln2"] = jnp.exp(NORM_LOG_STD * jax.random.normal(k2, (d,))).astype(jnp.bfloat16)
    return out


def draw_embed(seed_key, arch: Arch) -> jax.Array:
    """Tied embedding / lm head (padded_vocab, D) bf16."""
    k = jax.random.fold_in(seed_key, 2)
    return (jax.random.normal(k, (arch.padded_vocab, arch.d_model)) * EMBED_STD).astype(
        jnp.bfloat16
    )


def draw_final_norm(seed_key, arch: Arch) -> jax.Array:
    k = jax.random.fold_in(seed_key, 3)
    return jnp.exp(NORM_LOG_STD * jax.random.normal(k, (arch.d_model,))).astype(jnp.bfloat16)


def packed_layer(lkey, arch: Arch, layer) -> dict:
    """Layer ``layer`` as ``quantize_params`` emits it, unfused."""
    w = draw_layer(lkey, arch, layer)
    tree = {"attn": {}, "mlp": {}, "ln1": w["ln1"], "ln2": w["ln2"]}
    for name, (parent, *_) in arch.linears().items():
        tree[parent][name] = program.pack_linear(w[name], arch.a_bits, arch.group)
    return tree


def build_params(arch: Arch, seed: int) -> dict:
    """The unfused packed W4A4 tree from ``seed``, on the default device, in
    one jitted call (layers drawn and packed one at a time)."""

    @jax.jit
    def build(key):
        layers = jax.lax.map(
            lambda i: packed_layer(M.layer_key(key, i), arch, i),
            jnp.arange(arch.n_layers),
        )
        return {"embed": draw_embed(key, arch), "layers": layers,
                "ln_f": draw_final_norm(key, arch)}

    return build(M.root_key(seed))


def layer_cost(arch: Arch, m: int) -> costs.Cost:
    """The linears of one layer over ``m`` token rows, as the program fuses
    them: q, k and v in one call, gate and up in one."""
    lin = arch.linears()
    seg = {name: (n, arch.rank_of(k, n), min(arch.group, arch.rank_of(k, n)))
           for name, (_, k, n, _) in lin.items()}
    d, f, g = arch.d_model, arch.d_ff, arch.group
    return (costs.dual_gemm(m, d, [seg["q"], seg["k"], seg["v"]], g)
            + costs.dual_gemm(m, arch.n_heads * arch.head_dim, [seg["o"]], g)
            + costs.dual_gemm(m, d, [seg["gate"], seg["up"]], g)
            + costs.dual_gemm(m, f, [seg["down"]], g))


def launch_cost(arch: Arch, record, batch_slots: int) -> dict:
    """{"linear", "attention", "head"} Costs of one ragged launch, summed
    over layers, from the engine's ``StepRecord`` (``slot``, ``pos``,
    ``ctx``, ``logit_idx``): only rows that carry a token count, and the lm
    head only for slots whose logits row is one of them."""
    slot, pos, ctx, logit_idx = record.slot, record.pos, record.ctx, record.logit_idx
    real = slot < batch_slots
    layer = layer_cost(arch, int(real.sum()))
    slots = sorted(set(int(s) for s in slot[real]))
    att = costs.ragged_attention((pos[real] + 1).tolist(), [int(ctx[s]) for s in slots],
                                 arch.n_heads, arch.n_kv_heads, arch.head_dim)
    used = sum(1 for s in slots if int(slot[int(logit_idx[s])]) == s)
    n = arch.n_layers
    return {
        "linear": costs.Cost(layer.ops * n, layer.bytes * n),
        "attention": costs.Cost(att.ops * n, att.bytes * n),
        "head": costs.lm_head(used, arch.d_model, arch.vocab),
    }


def kv_bytes_per_token(arch: Arch) -> int:
    """Bytes of K and V (bf16) one token holds in the cache, over all layers."""
    return arch.n_layers * arch.n_kv_heads * arch.head_dim * 2 * 2
