"""Weights of the block-diffusion sparse-expert decoder (`model_type`
`sdar_moe`), made from the seed.

The rule is `weights.py`'s: a leaf's values depend only on (seed, leaf index,
shape, std), so the driver's one pass over the whole model and the
reference's later call for a single leaf give the same numbers.  The leaf
table is this architecture's own: grouped-query attention with an explicit
head size and a learned rmsnorm over each query and each key head, and in
EVERY layer a router over all the experts and the experts themselves,
stacked, gate and up side by side ([experts, in, 2 * width]); no bias, no
shared expert, no dense layer.
"""
import jax
import jax.numpy as jnp

from weights import seed_key

LAYER_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "q_norm",
                "k_norm", "o_proj", "post_norm", "router", "experts_w1",
                "experts_w2")


def leaf_specs(cfg):
    """[(name, shape, std, kind)] in a fixed order.  kind "norm": 1 + std * z;
    "matrix": std * z.  Matrices are [in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    fe, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    shapes = {
        "input_norm": ((h,), 0.1, "norm"),
        "q_proj": ((h, nq), h ** -0.5, "matrix"),
        "k_proj": ((h, nkv), h ** -0.5, "matrix"),
        "v_proj": ((h, nkv), h ** -0.5, "matrix"),
        "q_norm": ((hd,), 0.1, "norm"),
        "k_norm": ((hd,), 0.1, "norm"),
        "o_proj": ((nq, h), nq ** -0.5, "matrix"),
        "post_norm": ((h,), 0.1, "norm"),
        "router": ((h, e), h ** -0.5, "matrix"),
        "experts_w1": ((e, h, 2 * fe), h ** -0.5, "matrix"),
        "experts_w2": ((e, fe, h), fe ** -0.5, "matrix")}
    specs = [("embed", (v, h), h ** -0.5, "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        for leaf in LAYER_LEAVES:
            specs.append((f"layers.{i}.{leaf}",) + shapes[leaf])
    specs.append(("final_norm", (h,), 0.1, "norm"))
    specs.append(("lm_head", (h, v), h ** -0.5, "matrix"))
    return specs


def _leaf(key, index, shape, std, kind, dtype):
    z = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * std
    return (1.0 + z if kind == "norm" else z).astype(dtype)


# the index is traced: one program a (shape, std, kind), not one a leaf
_leaf_alone = jax.jit(_leaf, static_argnums=(2, 3, 4, 5))


def make_leaf(seed, cfg, name, dtype):
    """One leaf alone, in `dtype`: what leaves() gave it."""
    for index, (n, shape, std, kind) in enumerate(leaf_specs(cfg)):
        if n == name:
            return _leaf_alone(seed_key(seed), index, shape, std, kind,
                               jnp.dtype(dtype))
    raise KeyError(name)


def leaves(seed, cfg, dtype):
    """(name, array) of the whole model, leaf after leaf: the largest leaf's
    float32 draw (1.6 GB for 128 stacked experts' gate and up) is the most
    that stands beside what is already made."""
    key = seed_key(seed)
    for index, (name, shape, std, kind) in enumerate(leaf_specs(cfg)):
        yield name, _leaf_alone(key, index, shape, std, kind,
                                jnp.dtype(dtype))
