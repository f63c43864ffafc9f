"""The plain reference: a llama-architecture decoder in jax.numpy float32.

Forward, next-token loss, gradients and AdamW as the papers write them:
RMSNorm, rotary embedding (rotate-half), causal grouped-query attention
with a float32 softmax, SwiGLU, an untied head.  No kernels, no cache, no
batching tricks.  Every matrix product runs at `Precision.HIGHEST`; on a TPU
a float32 product is otherwise done in bfloat16 passes.

It imports nothing of `paddle_tpu` and is given nothing the program made:
its weights come from `benchmark/weights.py`, from the seed.

It is computed layer by layer so that it fits beside nothing else on one
16 GB chip: the backward pass keeps each layer's input and replays the layer
inside `jax.vjp`, and a leaf's gradient is handed on as soon as it is known.

`precision` picks the arithmetic of the matrix products with weights:
"float32" is the reference; "bfloat16" and "int8" are the CONTROLS, the
reference put in the program's place one precision below what a
configuration states (int8: symmetric, a scale per token and per output
channel, straight-through gradients).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as weights_mod

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_quant_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def weight_matmul(precision):
    """x [.., in] @ w [in, out] in the given precision, float32 out."""
    if precision == "float32":
        return lambda x, w: jnp.matmul(x, w, precision=HIGHEST)
    if precision == "bfloat16":
        return lambda x, w: jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    if precision == "int8":
        return lambda x, w: jnp.matmul(_fake_quant_int8(x, -1),
                                       _fake_quant_int8(w, 0),
                                       precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [S, heads, d], positions [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention_row(q, k, v):
    """Causal attention of one sequence: q [S, nh, d], k and v
    [S, nkv, d]; query head j reads key head j // (nh / nkv)."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    qg = q.reshape(s, nkv, nh // nkv, d)
    scores = jnp.einsum("sngd,tnd->ngst", qg, k,
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("ngst,tnd->sngd", probs, v, precision=HIGHEST)
    return out.reshape(s, nh * d)


def layer(p, x, cfg, precision):
    """One decoder block on x [B, S, H]; p holds the block's nine leaves."""
    mm = weight_matmul(precision)
    b, s, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    h = rms_norm(x, p["input_norm"], eps)
    q = mm(h, p["q_proj"]).reshape(b, s, nh, hd)
    k = mm(h, p["k_proj"]).reshape(b, s, nkv, hd)
    v = mm(h, p["v_proj"]).reshape(b, s, nkv, hd)

    def one(row):
        qr, kr, vr = row
        return attention_row(rope(qr, pos, theta), rope(kr, pos, theta), vr)
    attn = jax.lax.map(one, (q, k, v))
    x = x + mm(attn, p["o_proj"])
    h = rms_norm(x, p["post_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, p["gate_proj"])) * mm(h, p["up_proj"]),
                  p["down_proj"])


def head_logits(final_norm, lm_head, x, cfg, precision):
    return weight_matmul(precision)(
        rms_norm(x, final_norm, cfg["rms_norm_eps"]), lm_head)


def head_loss(final_norm, lm_head, x, ids, cfg, precision):
    """Mean next-token cross entropy: position t predicts ids[t + 1]."""
    logits = head_logits(final_norm, lm_head, x, cfg, precision)[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - picked)


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, precision):
    cfg = dict(cfg_items)
    fwd = jax.jit(lambda p, x: layer(p, x, cfg, precision))

    def bwd(p, x, dy):
        _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, cfg, precision), p, x)
        return vjp(dy)
    head = jax.jit(jax.value_and_grad(
        lambda n, w, x, ids: head_loss(n, w, x, ids, cfg, precision),
        argnums=(0, 1, 2)))
    head_fwd = jax.jit(
        lambda n, w, x, ids: head_loss(n, w, x, ids, cfg, precision))
    logits = jax.jit(
        lambda n, w, x: head_logits(n, w, x, cfg, precision))
    return fwd, jax.jit(bwd), head, head_fwd, logits


_embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0))
_embed_grad = jax.jit(
    lambda ids, dx, rows: jnp.zeros((rows, dx.shape[-1]), jnp.float32)
    .at[ids.reshape(-1)].add(dx.reshape(-1, dx.shape[-1])),
    static_argnums=(2,))


def _layer_params(params, i):
    return {leaf: params[f"layers.{i}.{leaf}"]
            for leaf in weights_mod.LAYER_LEAVES}


def forward_loss(params, ids, cfg, precision="float32"):
    fwd, _, _, head_fwd, _ = _programs(_frozen(cfg), precision)
    x = _embed(params["embed"], ids)
    for i in range(cfg["num_hidden_layers"]):
        x = fwd(_layer_params(params, i), x)
    return float(head_fwd(params["final_norm"], params["lm_head"], x, ids))


def loss_and_grads(params, ids, cfg, on_grad, precision="float32"):
    """The loss of one batch; `on_grad(name, gradient)` is called for every
    leaf as soon as its gradient is known, last layer first.  The caller
    may replace params[name] there: no later part of this backward pass
    reads that leaf again."""
    fwd, bwd, head, _, _ = _programs(_frozen(cfg), precision)
    depth = cfg["num_hidden_layers"]
    inputs = [_embed(params["embed"], ids)]
    for i in range(depth):
        inputs.append(fwd(_layer_params(params, i), inputs[-1]))
    loss, (d_norm, d_head, dx) = head(params["final_norm"],
                                      params["lm_head"], inputs.pop(), ids)
    on_grad("final_norm", d_norm)
    on_grad("lm_head", d_head)
    del d_norm, d_head
    for i in reversed(range(depth)):
        dp, dx = bwd(_layer_params(params, i), inputs.pop(), dx)
        for leaf in weights_mod.LAYER_LEAVES:
            on_grad(f"layers.{i}.{leaf}", dp.pop(leaf))
    on_grad("embed", _embed_grad(ids, dx, cfg["vocab_size"]))
    return float(loss)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6), donate_argnums=0)
def adamw(p, history, lr, wd, b1, b2, eps):
    """AdamW from a zero state over the gradients seen so far (oldest
    first), applied to the current value p: decoupled decay, bias
    correction, Loshchilov & Hutter 2019 algorithm 2."""
    m = v = jnp.zeros_like(p)
    for g in history:
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
    t = len(history)
    m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)


def train_reference(seed, cfg, batches, hyper, precision="float32"):
    """Follow the first steps of training from the seed's weights.

    batches: three [B, S] int32 arrays.  Two full steps are followed and
    the third batch's loss is taken at the twice-updated parameters.  The
    moments after step one are functions of the first gradient alone, so
    that gradient is kept (one copy of the model) and not m and v (two):
    parameters, m and v in float32 do not fit one chip beside a layer's
    backward pass.  Returns the three losses, each leaf's first-gradient
    norm and a sample of its elements, and each leaf's norm of change after
    the two steps."""
    lr, wd = hyper["learning_rate"], hyper["weight_decay"]
    b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["epsilon"]
    params = dict(weights_mod.make_weights(seed, cfg, "float32"))
    first, grad_norms, grad_samples, losses = {}, {}, {}, []

    def step_one(name, g):
        grad_norms[name] = weights_mod.norm(g)
        grad_samples[name] = weights_mod.sample(g, seed, cfg, name)
        first[name] = g
        params[name] = adamw(params[name], (g,), lr, wd, b1, b2, eps)

    def step_two(name, g):
        params[name] = adamw(params[name], (first.pop(name), g), lr, wd,
                             b1, b2, eps)

    ids = [jnp.asarray(b, jnp.int32) for b in batches]
    losses.append(loss_and_grads(params, ids[0], cfg, step_one, precision))
    losses.append(loss_and_grads(params, ids[1], cfg, step_two, precision))
    losses.append(forward_loss(params, ids[2], cfg, precision))
    change = {name: weights_mod.change_norm(params[name], seed, cfg, name,
                                            "float32")
              for name in list(params)}
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": change}


def teacher_forced_logits(seed, cfg, sequences, weight_dtype, pad_to,
                          precision="float32"):
    """For each (prompt, tokens) the logits [len(tokens), V] that predict
    each token, from ONE full causal forward over prompt + tokens.  The
    weights are made layer by layer, in the dtype they are served in, and
    widened to float32."""
    fwd, _, _, _, logits_of = _programs(_frozen(cfg), precision)

    def leaf(name):
        return weights_mod.make_leaf(seed, cfg, name,
                                     weight_dtype).astype(jnp.float32)
    ids = np.zeros((len(sequences), pad_to), np.int32)
    for r, (prompt, tokens) in enumerate(sequences):
        ids[r, :len(prompt)] = prompt
        ids[r, len(prompt):len(prompt) + len(tokens)] = tokens
    x = _embed(leaf("embed"), jnp.asarray(ids))
    for i in range(cfg["num_hidden_layers"]):
        x = fwd({n: leaf(f"layers.{i}.{n}")
                 for n in weights_mod.LAYER_LEAVES}, x)
    final_norm, lm_head = leaf("final_norm"), leaf("lm_head")
    out = []
    for r, (prompt, tokens) in enumerate(sequences):
        rows = logits_of(final_norm, lm_head, x[r:r + 1])[0]
        out.append(rows[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
    return out
