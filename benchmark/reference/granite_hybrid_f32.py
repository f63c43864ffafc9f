"""The plain reference of the hybrid state-space decoder (`model_type`
`granitemoehybrid`): jax.numpy, float32, under
`jax.default_matmul_precision("highest")` and `Precision.HIGHEST`.

The equations, x the residual stream [S, H] of one sequence, layer l of kind
`layer_types[l]`:

  x = embed(ids) * embedding_multiplier
  x += residual_multiplier * mixer(rmsnorm(x));  mixer by kind:
    mamba:  [z | xBC | dt] = h W_in;  xBC = silu(conv(xBC) + conv_bias), a
            causal depthwise conv of mamba_d_conv taps (tap k multiplies the
            input d_conv - 1 - k positions back; zeros before position 0);
            [x | B | C] = xBC, x as H heads of P;
            dt = softplus(dt + dt_bias);  A = -exp(A_log)
            h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,  h_{-1} = 0
            y_t = h_t C_t + D x_t
            out = rmsnorm(y * silu(z); mixer_norm) W_out
    attention:  q = h Wq (nh heads of d), k = h Wk, v = h Wv (nkv heads),
            no rotary; lane i attends row j iff j <= i; scores times
            attention_multiplier, softmax, (softmax . v) Wo; query head n
            reads key head n // (nh / nkv)
  x += residual_multiplier * down(silu(gate h) * up h),  h = rmsnorm(x)
  logits = rmsnorm(x) embed^T / logits_scaling

The state-space recurrence is a `lax.scan` over the tokens, one token after
another: not the chunked form the program's kernel computes, so the two
share no algorithm.  No cache, no batching, no kernels; it imports nothing of
`paddle_tpu`.  Departures from the published modelling code (Hugging Face
`GraniteMoeHybrid`): none in the equations; the split order of the
in-projection and of the MLP's fused input is the configuration's `assumed`
one (a permutation of random weights), and dt_bias, A_log and D are drawn
as the configuration's `assumed` says.  Weights come from
`benchmark/weights_granite_hybrid.py`, layer by layer.

`precision` picks the arithmetic of the products with weights, as
`decoder_f32.weight_matmul` defines it ("float32" the reference, "int8" the
control one precision below bfloat16); the recurrence stays float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights_granite_hybrid as weights_mod
from reference.decoder_f32 import HIGHEST, rms_norm, weight_matmul

QUERY_BLOCK = 256       # query rows whose scores stand together
BUCKET = 512            # teacher_forced_logits pads a sequence to these


def _mixer_lanes(p, h, cfg, mm):
    """h [S, H] normed -> z, and the recurrence's x [S, heads, P], B and C
    [S, N], dt [S, heads] (after its softplus) and A [heads]."""
    s = h.shape[0]
    inner, conv, heads = weights_mod.mamba_widths(cfg)
    P, N, K = cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    zxbcdt = mm(h, p["in_proj"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    padded = jnp.concatenate([jnp.zeros((K - 1, conv), jnp.float32), xbc])
    xbc = jax.nn.silu(sum(padded[k:k + s] * p["conv_weight"][k]
                          for k in range(K)) + p["conv_bias"])
    x = xbc[:, :inner].reshape(s, heads, P)
    b, c = xbc[:, inner:inner + N], xbc[:, inner + N:]
    return z, x, b, c, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"])


def _recurrence(p, x, b, c, dt, a):
    """The state after the last token [heads, P, N], and y [S, heads, P]."""
    def token(state, t):
        x_t, b_t, c_t, dt_t = t
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y = jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST) \
            + p["D"][:, None] * x_t
        return state, y
    heads, P = x.shape[1:]
    return jax.lax.scan(token, jnp.zeros((heads, P, b.shape[1]), jnp.float32),
                        (x, b, c, dt))


def mamba_mixer(p, h, cfg, mm):
    """h [S, H] normed -> [S, H]: the Mamba-2 mixer of one sequence."""
    z, x, b, c, dt, a = _mixer_lanes(p, h, cfg, mm)
    _, y = _recurrence(p, x, b, c, dt, a)
    g = y.reshape(h.shape[0], -1) * jax.nn.silu(z)
    return mm(rms_norm(g, p["mixer_norm"], cfg["rms_norm_eps"]), p["out_proj"])


def mixer_state(p, h, cfg, mm, n):
    """The mixer's state [heads, P, N] after the first n tokens of h [S, H]
    (normed): the tokens past them carry dt 0 and leave it as it was."""
    _, x, b, c, dt, a = _mixer_lanes(p, h, cfg, mm)
    dt = jnp.where(jnp.arange(h.shape[0])[:, None] < n, dt, 0.0)
    state, _ = _recurrence(p, x, b, c, dt, a)
    return state


def attention(p, h, cfg, mm):
    """h [S, H] normed -> [S, H]: causal GQA without rotary."""
    s = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    q = mm(h, p["q_proj"]).reshape(s, nkv, nh // nkv, d)
    k = mm(h, p["k_proj"]).reshape(s, nkv, d)
    v = mm(h, p["v_proj"]).reshape(s, nkv, d)
    block = min(s, QUERY_BLOCK)
    col = jnp.arange(s)

    def rows(part):
        first, q_rows = part
        seen = col[None, :] <= (first + jnp.arange(block))[:, None]
        scores = jnp.einsum("qngd,knd->ngqk", q_rows, k, precision=HIGHEST) \
            * cfg["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               -1)
        return jnp.einsum("ngqk,knd->qngd", probs, v, precision=HIGHEST)
    out = jax.lax.map(rows, (jnp.arange(0, s, block),
                             q.reshape(s // block, block, nkv, nh // nkv, d)))
    return mm(out.reshape(s, nh * d), p["o_proj"])


def layer(p, x, cfg, precision, mamba):
    """One decoder layer on x [S, H]."""
    mm = weight_matmul(precision)
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba_mixer if mamba else attention
    x = x + m * mixer(p, rms_norm(x, p["input_norm"], eps), cfg, mm)
    h = rms_norm(x, p["post_norm"], eps)
    return x + m * mm(jax.nn.silu(mm(h, p["gate_proj"])) * mm(h, p["up_proj"]),
                      p["down_proj"])


# the keys of a configuration file the equations read
_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "shared_intermediate_size", "mamba_n_heads",
         "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
         "rms_norm_eps", "attention_multiplier", "residual_multiplier",
         "embedding_multiplier", "logits_scaling")


def _frozen(cfg):
    return tuple((k, cfg[k]) for k in _KEYS)


@functools.lru_cache(maxsize=None)
def _layer(cfg_items, precision, mamba):
    cfg = dict(cfg_items)

    def fwd(p, x):
        with jax.default_matmul_precision("highest"):
            return layer(p, x, cfg, precision, mamba)
    return jax.jit(fwd)


@functools.lru_cache(maxsize=None)
def _state(cfg_items, precision):
    cfg = dict(cfg_items)

    def state(p, x, n):
        with jax.default_matmul_precision("highest"):
            return mixer_state(p, rms_norm(x, p["input_norm"],
                                           cfg["rms_norm_eps"]),
                               cfg, weight_matmul(precision), n)
    return jax.jit(state)


@functools.lru_cache(maxsize=None)
def _head(cfg_items, precision):
    cfg = dict(cfg_items)

    def logits(final_norm, embed, x):
        with jax.default_matmul_precision("highest"):
            return weight_matmul(precision)(
                rms_norm(x, final_norm, cfg["rms_norm_eps"]), embed.T) \
                / cfg["logits_scaling"]
    return jax.jit(logits)


@functools.lru_cache(maxsize=None)
def _embed(multiplier):
    return jax.jit(lambda table, ids: jnp.take(table, ids, axis=0)
                   * multiplier)


def _run_layer(cfg, i, precision, p, x):
    return _layer(_frozen(cfg), precision, weights_mod.mamba_layer(cfg, i))(
        p, x)


def forward_logits(params, ids, cfg, precision="float32"):
    """Logits [S, V] of one full causal forward of ids [S]; params: {name:
    float32 array} of the whole model (tiny sizes: the tests)."""
    x = _embed(cfg["embedding_multiplier"])(params["embed"], jnp.asarray(ids))
    for i in range(cfg["num_hidden_layers"]):
        x = _run_layer(cfg, i, precision,
                       {n: params[f"layers.{i}.{n}"]
                        for n in weights_mod.layer_leaves(cfg, i)}, x)
    return _head(_frozen(cfg), precision)(params["final_norm"],
                                          params["embed"], x)


def teacher_forced_logits(seed, cfg, sequences, weight_dtype, pad_to,
                          precision="float32", probe=None):
    """For each (prompt, tokens) the logits [len(tokens), V] that predict
    each token, from ONE full causal forward over prompt + tokens.  The
    weights are made layer by layer, in the dtype they are served in, and
    widened to float32; a sequence at a time, padded to whole BUCKETs (at
    most `pad_to`): what lies past a sequence's end reaches none of its
    positions.  `probe`: None, or the ids [n] a slot's state has taken in;
    then (logits, states): the mamba layers' states after those n tokens,
    [mamba layers, heads, P, N] float32, from the same weights."""
    def leaf(name):
        return weights_mod.make_leaf(seed, cfg, name,
                                     weight_dtype).astype(jnp.float32)

    def padded(ids):
        n = len(ids)
        out = np.zeros((min(-(-n // BUCKET) * BUCKET, int(pad_to)),),
                       np.int32)
        out[:n] = ids
        return _embed(cfg["embedding_multiplier"])(embed, jnp.asarray(out))
    embed = leaf("embed")
    xs = [padded(np.concatenate([prompt, tokens]))
          for prompt, tokens in sequences]
    if probe is not None:
        xs.append(padded(np.asarray(probe, np.int32)))
    states = []
    for i in range(cfg["num_hidden_layers"]):
        p = {n: leaf(f"layers.{i}.{n}")
             for n in weights_mod.layer_leaves(cfg, i)}
        if probe is not None and weights_mod.mamba_layer(cfg, i):
            states.append(_state(_frozen(cfg), precision)(p, xs[-1],
                                                          len(probe)))
        xs = [_run_layer(cfg, i, precision, p, x) for x in xs]
        del p
    final_norm = leaf("final_norm")
    out = []
    for x, (prompt, tokens) in zip(xs, sequences):
        rows = _head(_frozen(cfg), precision)(final_norm, embed, x)
        out.append(rows[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
    if probe is None:
        return out
    return out, np.stack([np.asarray(s) for s in states])
