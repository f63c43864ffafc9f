"""Weights of the latent-attention, sparse-expert decoder, made from the seed.

The rule is `weights.py`'s: a leaf's values depend only on (seed, leaf index,
shape, std), so the driver's one pass over the whole model and the
reference's later call for a single leaf give the same numbers.  The leaf
table is this architecture's own (`model_type` `sarvam_mla`): MLA's six
leaves, a dense SwiGLU in the first `first_k_dense_replace` layers, and in
the others the router, its selection bias, the experts HELD here stacked
(`num_experts` of them: the configuration file's share) and the shared
expert.  Expert matrices hold gate and up side by side ([in, 2 * width]).
The selection bias is float32 whatever the cell's dtype, std 0.05, so that
leaving it out changes which experts are chosen (`assumed` (c)).
"""
import jax
import jax.numpy as jnp

from weights import seed_key

ATTENTION_LEAVES = ("input_norm", "q_proj", "q_norm", "kv_a_proj", "kv_norm",
                    "kv_b_proj", "o_proj", "post_norm")
DENSE_LEAVES = ("gate_proj", "up_proj", "down_proj")
EXPERT_LEAVES = ("router", "router_bias", "experts_w1", "experts_w2",
                 "shared_w1", "shared_w2")


def layer_leaves(cfg, i):
    return ATTENTION_LEAVES + (DENSE_LEAVES if i < cfg["first_k_dense_replace"]
                               else EXPERT_LEAVES)


def leaf_specs(cfg):
    """[(name, shape, std, kind)] in a fixed order.  kind "norm": 1 + std * z;
    "bias": std * z in float32; "matrix": std * z.  Matrices are [in, out]."""
    h, v, nh = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    q_head = cfg["qk_nope_head_dim"] + rope
    kv_head = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    held, width = cfg["num_experts"], cfg["router_width"]
    shapes = {
        "input_norm": ((h,), 0.1, "norm"),
        "q_proj": ((h, nh * q_head), h ** -0.5, "matrix"),
        "q_norm": ((q_head,), 0.1, "norm"),
        "kv_a_proj": ((h, rank + rope), h ** -0.5, "matrix"),
        "kv_norm": ((rank,), 0.1, "norm"),
        "kv_b_proj": ((rank, nh * kv_head), rank ** -0.5, "matrix"),
        "o_proj": ((nh * cfg["v_head_dim"], h),
                   (nh * cfg["v_head_dim"]) ** -0.5, "matrix"),
        "post_norm": ((h,), 0.1, "norm"),
        "gate_proj": ((h, f), h ** -0.5, "matrix"),
        "up_proj": ((h, f), h ** -0.5, "matrix"),
        "down_proj": ((f, h), f ** -0.5, "matrix"),
        "router": ((h, width), h ** -0.5, "matrix"),
        "router_bias": ((width,), 0.05, "bias"),
        "experts_w1": ((held, h, 2 * fe), h ** -0.5, "matrix"),
        "experts_w2": ((held, fe, h), fe ** -0.5, "matrix"),
        "shared_w1": ((h, 2 * fs), h ** -0.5, "matrix"),
        "shared_w2": ((fs, h), fs ** -0.5, "matrix")}
    specs = [("embed", (v, h), h ** -0.5, "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        for leaf in layer_leaves(cfg, i):
            specs.append((f"layers.{i}.{leaf}",) + shapes[leaf])
    specs.append(("final_norm", (h,), 0.1, "norm"))
    specs.append(("lm_head", (h, v), h ** -0.5, "matrix"))
    return specs


def _leaf(key, index, shape, std, kind, dtype):
    z = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * std
    if kind == "bias":
        return z
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
    float32 draw (2.1 GB for 32 stacked experts) is the most that stands
    beside what is already made."""
    key = seed_key(seed)
    for index, (name, shape, std, kind) in enumerate(leaf_specs(cfg)):
        yield name, _leaf_alone(key, index, shape, std, kind,
                                jnp.dtype(dtype))
