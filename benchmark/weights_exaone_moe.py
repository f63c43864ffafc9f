"""Weights of the window-and-full-attention sparse-expert decoder
(`model_type` `exaone_moe`), made from the seed.

The rule is `weights.py`'s: a leaf's values depend only on (seed, leaf index,
shape, std), so the driver's one pass over the whole model and the
reference's later call for a single leaf give the same numbers.  The leaf
table is this architecture's own: grouped-query attention with an explicit
head size and a learned rmsnorm over each query and each key head (the same
eight leaves in a sliding and in a full layer: the kind is the mask's and
the rotary's), a dense SwiGLU in the layers `mlp_layer_types` calls `dense`,
and in the `sparse` ones the router over its whole width, its selection
bias, the experts HELD here stacked (`num_experts` of them: the
configuration file's share; gate and up side by side, [in, 2 * width]) and
the shared expert.  The selection bias is float32 whatever the cell's dtype,
std 0.05, so that leaving it out changes which experts are chosen
(`assumed`).
"""
import jax.numpy as jnp

from weights import seed_key
from weights_mla_moe import _leaf_alone     # the same three kinds of leaf

ATTENTION_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "q_norm",
                    "k_norm", "o_proj", "post_norm")
DENSE_LEAVES = ("gate_proj", "up_proj", "down_proj")
EXPERT_LEAVES = ("router", "router_bias", "experts_w1", "experts_w2",
                 "shared_w1", "shared_w2")


def dense_layer(cfg, i):
    return cfg["mlp_layer_types"][i] == "dense"


def layer_leaves(cfg, i):
    return ATTENTION_LEAVES + (DENSE_LEAVES if dense_layer(cfg, i)
                               else EXPERT_LEAVES)


def leaf_specs(cfg):
    """[(name, shape, std, kind)] in a fixed order.  kind "norm": 1 + std * z;
    "bias": std * z in float32; "matrix": std * z.  Matrices are [in, out]."""
    h, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    held, width = cfg["num_experts"], cfg["router_width"]
    shapes = {
        "input_norm": ((h,), 0.1, "norm"),
        "q_proj": ((h, nq), h ** -0.5, "matrix"),
        "k_proj": ((h, nkv), h ** -0.5, "matrix"),
        "v_proj": ((h, nkv), h ** -0.5, "matrix"),
        "q_norm": ((hd,), 0.1, "norm"),
        "k_norm": ((hd,), 0.1, "norm"),
        "o_proj": ((nq, h), nq ** -0.5, "matrix"),
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


def make_leaf(seed, cfg, name, dtype):
    """One leaf alone, in `dtype`: what leaves() gave it."""
    for index, (n, shape, std, kind) in enumerate(leaf_specs(cfg)):
        if n == name:
            return _leaf_alone(seed_key(seed), index, shape, std, kind,
                               jnp.dtype(dtype))
    raise KeyError(name)


def leaves(seed, cfg, dtype):
    """(name, array) of the whole model, leaf after leaf: the largest leaf's
    float32 draw (1.6 GB for 16 stacked experts' gate and up) is the most
    that stands beside what is already made."""
    key = seed_key(seed)
    for index, (name, shape, std, kind) in enumerate(leaf_specs(cfg)):
        yield name, _leaf_alone(key, index, shape, std, kind,
                                jnp.dtype(dtype))
