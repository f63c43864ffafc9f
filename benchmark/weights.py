"""Weights of a llama-architecture decoder, made from the seed.

The benchmark owns the weights: the drivers put them into the program's
model, and the reference makes the same values again, leaf by leaf, once
the program's state is gone.  A leaf's values depend only on (seed, leaf
index, shape, std), so one jitted call for the whole model and a later call
for a single leaf give the same numbers.
"""
import jax
import jax.numpy as jnp

LAYER_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj",
                "post_norm", "gate_proj", "up_proj", "down_proj")


def leaf_specs(cfg):
    """[(name, shape, std, kind)] in a fixed order; kind "norm" leaves are
    1 + std * z, the others std * z.  Matrices are [in, out]."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    specs = [("embed", (v, h), h ** -0.5, "matrix")]
    shapes = {"input_norm": ((h,), 0.1, "norm"),
              "q_proj": ((h, nq), h ** -0.5, "matrix"),
              "k_proj": ((h, nkv), h ** -0.5, "matrix"),
              "v_proj": ((h, nkv), h ** -0.5, "matrix"),
              "o_proj": ((nq, h), nq ** -0.5, "matrix"),
              "post_norm": ((h,), 0.1, "norm"),
              "gate_proj": ((h, f), h ** -0.5, "matrix"),
              "up_proj": ((h, f), h ** -0.5, "matrix"),
              "down_proj": ((f, h), f ** -0.5, "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for leaf in LAYER_LEAVES:
            specs.append((f"layers.{i}.{leaf}",) + shapes[leaf])
    specs.append(("final_norm", (h,), 0.1, "norm"))
    specs.append(("lm_head", (h, v), h ** -0.5, "matrix"))
    return specs


def seed_key(seed):
    """A key for any whole-number seed, also one past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, index, shape, std, kind, dtype):
    z = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * std
    return (1.0 + z if kind == "norm" else z).astype(dtype)


_leaf_alone = jax.jit(_leaf, static_argnums=(1, 2, 3, 4, 5))


def make_leaf(seed, cfg, name, dtype):
    """One leaf alone, in `dtype`: what make_weights gave it."""
    for index, (n, shape, std, kind) in enumerate(leaf_specs(cfg)):
        if n == name:
            return _leaf_alone(seed_key(seed), index, shape, std, kind,
                               jnp.dtype(dtype))
    raise KeyError(name)


def make_weights(seed, cfg, dtype):
    """{name: array} for the whole model, in one jitted call on the
    default device, in the dtype the cell holds its parameters in."""
    specs = leaf_specs(cfg)

    def build(key):
        return {n: _leaf(key, i, shape, std, kind, jnp.dtype(dtype))
                for i, (n, shape, std, kind) in enumerate(specs)}
    return jax.jit(build)(seed_key(seed))


_norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
_diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
    a.astype(jnp.float32) - b.astype(jnp.float32)))))


def norm(value):
    return float(_norm(value))


def change_norm(value, seed, cfg, name, dtype):
    """Norm of (value - what the seed gave this leaf)."""
    return float(_diff_norm(value, make_leaf(seed, cfg, name, dtype)))


SAMPLE = 65536


def sample(value, seed, cfg, name):
    """SAMPLE elements of a leaf, at places drawn from (seed, leaf): the
    same places for the program's gradient and the reference's, so that the
    two can be compared element by element without keeping either whole."""
    import numpy as np
    index = [n for n, _, _, _ in leaf_specs(cfg)].index(name)
    places = np.random.default_rng([int(seed), 5, index]).integers(
        0, value.size, min(SAMPLE, value.size))
    return np.asarray(_take(value, jnp.asarray(places)))


_take = jax.jit(lambda a, places: jnp.take(a.reshape(-1), places)
                .astype(jnp.float32))
