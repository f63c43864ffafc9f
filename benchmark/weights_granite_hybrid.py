"""Weights of the hybrid state-space decoder (`model_type`
`granitemoehybrid`), made from the seed.

The rule is `weights.py`'s: a leaf's values depend only on (seed, leaf index,
shape, std, kind), so the driver's one pass over the whole model and the
reference's later call for a single leaf give the same numbers.  The leaf
table is this architecture's own: a `mamba` layer holds its mixer's leaves
(the in-projection [hidden, z | xBC | dt], the conv's taps [d_conv,
channels] and bias, dt_bias, A_log, D, the gated norm's weight and the
out-projection), an `attention` layer grouped-query attention without bias;
both an rmsnorm in front of each sublayer and a SwiGLU MLP.  The head is
the embedding (tied), drawn at std hidden ** -0.5 / embedding_multiplier.  dt_bias, A_log and D are float32 whatever the cell's
dtype and drawn as Mamba-2 initialises them (`assumed`): A = -exp(A_log) in
[-16, -1] and softplus(dt_bias) log-uniform in [0.001, 0.1].
"""
import functools
import math

import jax
import jax.numpy as jnp

from weights import seed_key

MAMBA_LEAVES = ("input_norm", "in_proj", "conv_weight", "conv_bias",
                "dt_bias", "A_log", "D", "mixer_norm", "out_proj")
ATTENTION_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj")
MLP_LEAVES = ("post_norm", "gate_proj", "up_proj", "down_proj")
DT_RANGE = (0.001, 0.1)
A_RANGE = (1.0, 16.0)
# the leaves held in float32 whatever the serving dtype
FLOAT32 = ("dt_bias", "A_log", "D")


def mamba_layer(cfg, i):
    return cfg["layer_types"][i] == "mamba"


def layer_leaves(cfg, i):
    return (MAMBA_LEAVES if mamba_layer(cfg, i) else ATTENTION_LEAVES) \
        + MLP_LEAVES


def mamba_widths(cfg):
    """(inner, conv channels, heads) of a mamba layer."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, conv, cfg["mamba_n_heads"]


def leaf_specs(cfg):
    """[(name, shape, std, kind)] in a fixed order.  kind "norm": 1 + std * z;
    "matrix" and "vector": std * z; "a_log": log of a uniform draw over
    A_RANGE; "dt_bias": the inverse softplus of a log-uniform draw over
    DT_RANGE.  Matrices are [in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    f = cfg["shared_intermediate_size"]
    d = h // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    inner, conv, heads = mamba_widths(cfg)
    k = cfg["mamba_d_conv"]
    shapes = {
        "input_norm": ((h,), 0.1, "norm"),
        "in_proj": ((h, inner + conv + heads), h ** -0.5, "matrix"),
        "conv_weight": ((k, conv), k ** -0.5, "matrix"),
        "conv_bias": ((conv,), 0.1, "vector"),
        "dt_bias": ((heads,), 0.0, "dt_bias"),
        "A_log": ((heads,), 0.0, "a_log"),
        "D": ((heads,), 0.1, "norm"),
        "mixer_norm": ((inner,), 0.1, "norm"),
        "out_proj": ((inner, h), inner ** -0.5, "matrix"),
        "q_proj": ((h, nq), h ** -0.5, "matrix"),
        "k_proj": ((h, nkv), h ** -0.5, "matrix"),
        "v_proj": ((h, nkv), h ** -0.5, "matrix"),
        "o_proj": ((nq, h), nq ** -0.5, "matrix"),
        "post_norm": ((h,), 0.1, "norm"),
        "gate_proj": ((h, f), h ** -0.5, "matrix"),
        "up_proj": ((h, f), h ** -0.5, "matrix"),
        "down_proj": ((f, h), f ** -0.5, "matrix")}
    # the embedding enters the residual stream times embedding_multiplier:
    # drawn at h ** -0.5 / multiplier, a scaled row has the unit norm the
    # other benchmark models' rows have (at h ** -0.5 the tied head would
    # put each token's own row first and serve its input back, whatever
    # the layers compute: `assumed`)
    specs = [("embed", (v, h), h ** -0.5 / cfg["embedding_multiplier"],
              "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        for leaf in layer_leaves(cfg, i):
            specs.append((f"layers.{i}.{leaf}",) + shapes[leaf])
    specs.append(("final_norm", (h,), 0.1, "norm"))
    return specs


# the leaf's index is an operand: one program a (shape, std, kind, dtype),
# about twenty for the whole model, where a static index would compile one a
# leaf (480 here)
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _leaf(key, index, shape, std, kind, dtype):
    key = jax.random.fold_in(key, index)
    if kind == "a_log":
        u = jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
        return jnp.log(u)
    if kind == "dt_bias":
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(key, shape, jnp.float32) * std
    return (1.0 + z if kind == "norm" else z).astype(dtype)


def _dtype(name, dtype):
    return jnp.dtype(jnp.float32 if name.rsplit(".", 1)[-1] in FLOAT32
                     else dtype)


def make_leaf(seed, cfg, name, dtype):
    """One leaf alone, in `dtype` (float32 for FLOAT32): what leaves() gave
    it."""
    for index, (n, shape, std, kind) in enumerate(leaf_specs(cfg)):
        if n == name:
            return _leaf(seed_key(seed), index, shape, std, kind,
                         _dtype(name, dtype))
    raise KeyError(name)


def leaves(seed, cfg, dtype):
    """(name, array) of the whole model, leaf after leaf."""
    key = seed_key(seed)
    for index, (name, shape, std, kind) in enumerate(leaf_specs(cfg)):
        yield name, _leaf(key, index, shape, std, kind, _dtype(name, dtype))
