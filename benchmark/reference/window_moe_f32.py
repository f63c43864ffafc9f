"""The plain reference of the window-and-full-attention sparse-expert decoder
(`model_type` `exaone_moe`): jax.numpy, float32, `Precision.HIGHEST`.

The equations, layer l of kind `layer_types[l]` (`sliding_attention` |
`full_attention`) with an MLP of kind `mlp_layer_types[l]`, x `[S, H]`:

  h = rmsnorm(x);  q = h Wq as nh heads of d, k = h Wk, v = h Wv as nkv
  heads of d;  q and k each through a learned d-wide rmsnorm over every head
  (g_q, g_k);  in a SLIDING layer q and k take the rotary (rotate-half,
  `rope_parameters.rope_theta`), in a FULL layer they take none;
  lane i attends row j iff j <= i, and in a sliding layer also
  i - j < sliding_window (the token itself and the W - 1 before it);
  scores over sqrt(d), softmax, x += (softmax . v) Wo;  query head n reads
  key head n // (nh / nkv).

  h = rmsnorm(x).  dense:  x += down(silu(gate h) * up h).
  sparse:  s = sigmoid(h Wr) over the router's whole width; the top k of
  s + b; w_e = routed_scaling_factor * s_e / sum of the chosen s;
  x += shared(h) + sum_e w_e expert_e(h), every expert
  down(silu(gate h) * up h); no token dropped.

  After the last layer rmsnorm, then the head.

A loop (`lax.scan`) over the experts HELD here (the configuration's share:
experts `experts_held[0]` .. of `router_width`; what the absent ones would
add is left out, as in the program), attention over blocks of query rows
(the scores of a block, [heads, block, S], are the largest thing it makes),
no cache, no kernels, no batching.  Weights come from
`benchmark/weights_exaone_moe.py`, layer by layer, so the most it holds is
one layer's float32 experts.  It imports nothing of `paddle_tpu`.

`precision` picks the arithmetic of the products with weights, as
`decoder_f32.weight_matmul` defines it ("float32" the reference, "int8" the
control one precision below bfloat16).  `fault` plants ONE departure from
the equations, for the readings the limits of `correct` are set from
(`benchmark/control_window.py`); None in every benchmark run.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights_exaone_moe as weights_mod
from reference.decoder_f32 import HIGHEST, rms_norm, rope, weight_matmul

ATTENTION_FAULTS = ("window_ignored", "window_one_short",
                    "rope_on_full_layer", "no_rope_on_sliding", "no_qk_norm")
EXPERT_FAULTS = ("no_shared_expert", "no_router_bias", "top_k_minus_1",
                 "renormalised_over_held")
FAULTS = ATTENTION_FAULTS + EXPERT_FAULTS

QUERY_BLOCK = 256       # query rows whose scores stand together
BUCKET = 2048           # teacher_forced_logits pads a sequence to these


def attend(q, k, v, window):
    """One sequence: q [S, nkv, g, d], k and v [S, nkv, d] -> [S, nkv*g*d];
    `window` 0: causal; W: causal and i - j < W."""
    s, nkv, g, d = q.shape
    block = s if s <= QUERY_BLOCK else QUERY_BLOCK
    if s % block:
        raise ValueError(f"{s} rows are not whole blocks of {block}")
    col = jnp.arange(s)

    def rows(part):
        first, q_rows = part                       # [block, nkv, g, d]
        row = first + jnp.arange(block)
        seen = col[None, :] <= row[:, None]
        if window:
            seen &= row[:, None] - col[None, :] < window
        scores = jnp.einsum("qngd,knd->ngqk", q_rows, k,
                            precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               -1)
        return jnp.einsum("ngqk,knd->qngd", probs, v, precision=HIGHEST)
    out = jax.lax.map(rows, (jnp.arange(0, s, block),
                             q.reshape(s // block, block, nkv, g, d)))
    return out.reshape(s, nkv * g * d)


def attention(p, h, cfg, mm, sliding, fault=None):
    """h [B, S, H] normed -> [B, S, H]; `sliding`: the layer's kind."""
    b, s, _ = h.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_parameters"]["rope_theta"]
    q = mm(h, p["q_proj"]).reshape(b, s, nh, d)
    k = mm(h, p["k_proj"]).reshape(b, s, nkv, d)
    v = mm(h, p["v_proj"]).reshape(b, s, nkv, d)
    if fault != "no_qk_norm":
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    rotary = sliding
    if fault == "rope_on_full_layer":
        rotary = True
    if fault == "no_rope_on_sliding":
        rotary = False
    window = cfg["sliding_window"] if sliding else 0
    if fault == "window_ignored":
        window = 0
    if fault == "window_one_short" and window:
        window -= 1
    pos = jnp.arange(s)

    def one(row):
        q_row, k_row, v_row = row
        if rotary:
            q_row, k_row = rope(q_row, pos, theta), rope(k_row, pos, theta)
        return attend(q_row.reshape(s, nkv, nh // nkv, d), k_row, v_row,
                      window)
    return mm(jax.lax.map(one, (q, k, v)), p["o_proj"])


def swiglu(x, w1, w2, mm):
    """down(silu(gate x) * up x) with gate | up side by side in w1."""
    gu = mm(x, w1)
    half = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], w2)


def routing(x, router, bias, cfg, fault=None):
    """(chosen expert ids [.., k], their weights [.., k]) over the router's
    whole width (`n_group` 1: one group), in float32 whatever `precision`:
    the router is not a weight product a serving dtype touches."""
    k = cfg["num_experts_per_tok"] - (fault == "top_k_minus_1")
    s = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    pick = s if fault == "no_router_bias" else s + bias
    _, chosen = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, chosen, -1)
    first, count = cfg["experts_held"][0], cfg["num_experts"]
    norm = w
    if fault == "renormalised_over_held":
        norm = jnp.where((chosen >= first) & (chosen < first + count), w, 0.0)
    w = w / jnp.maximum(jnp.sum(norm, -1, keepdims=True), 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def expert_layer(p, h, cfg, mm, fault=None):
    """shared(h) + the part of sum_e w_e expert_e(h) that the experts held
    here give."""
    chosen, w = routing(h, p["router"], p["router_bias"], cfg, fault)
    y = jnp.zeros_like(h) if fault == "no_shared_expert" \
        else swiglu(h, p["shared_w1"], p["shared_w2"], mm)
    first = cfg["experts_held"][0]

    def held(y, expert):
        j, w1, w2 = expert
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), -1)
        return y + w_j[..., None] * swiglu(h, w1, w2, mm), None
    return jax.lax.scan(held, y, (jnp.arange(cfg["num_experts"]),
                                  p["experts_w1"], p["experts_w2"]))[0]


def attention_half(p, x, cfg, precision, sliding, fault=None):
    """x + attention(rmsnorm(x)): the layer's first residual."""
    return x + attention(p, rms_norm(x, p["input_norm"], cfg["rms_norm_eps"]),
                         cfg, weight_matmul(precision), sliding, fault)


def mlp_half(p, x, cfg, precision, dense, fault=None):
    """x + mlp(rmsnorm(x)): the layer's second residual, dense or sparse."""
    mm = weight_matmul(precision)
    h = rms_norm(x, p["post_norm"], cfg["rms_norm_eps"])
    if dense:
        return x + mm(jax.nn.silu(mm(h, p["gate_proj"]))
                      * mm(h, p["up_proj"]), p["down_proj"])
    return x + expert_layer(p, h, cfg, mm, fault=fault)


def head_logits(final_norm, lm_head, x, cfg, precision):
    return weight_matmul(precision)(
        rms_norm(x, final_norm, cfg["rms_norm_eps"]), lm_head)


# the keys of a configuration file the equations read
_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size",
         "moe_intermediate_size", "num_shared_experts", "num_experts",
         "router_width", "experts_held", "num_experts_per_tok",
         "routed_scaling_factor", "rms_norm_eps", "rope_parameters",
         "sliding_window", "num_hidden_layers")


def _frozen(cfg):
    """Those keys as a hashable key of the compiled programs."""
    return tuple((k, tuple(sorted(cfg[k].items())) if k == "rope_parameters"
                  else tuple(cfg[k]) if k == "experts_held" else cfg[k])
                 for k in _KEYS)


def _thaw(items):
    return {k: dict(v) if k == "rope_parameters" else v for k, v in items}


@functools.lru_cache(maxsize=None)
def _half(cfg_items, precision, which, kind, fault):
    """The jitted half of a layer: `which` "attention" (kind: sliding?) or
    "mlp" (kind: dense?).  One program a (half, kind, fault THAT HALF
    reads): a fault of the other half takes the sound half's program, so a
    control that judges many faults compiles each half once."""
    cfg = _thaw(cfg_items)
    half = attention_half if which == "attention" else mlp_half
    return jax.jit(lambda p, x: half(p, x, cfg, precision, kind, fault))


@functools.lru_cache(maxsize=None)
def _head(cfg_items, precision):
    cfg = _thaw(cfg_items)
    return jax.jit(lambda n, w, x: head_logits(n, w, x, cfg, precision))


def _layer(cfg, i, precision, fault):
    """Layer i as a function of (its leaves, x)."""
    items = _frozen(cfg)
    attend = _half(items, precision, "attention",
                   cfg["layer_types"][i] == "sliding_attention",
                   fault if fault in ATTENTION_FAULTS else None)
    mlp = _half(items, precision, "mlp", weights_mod.dense_layer(cfg, i),
                fault if fault in EXPERT_FAULTS else None)
    return lambda p, x: mlp(p, attend(p, x))


_embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0))


def forward_logits(params, ids, cfg, precision="float32", fault=None):
    """Logits [B, S, V] of one full causal forward; params: {name: float32
    array} of the whole model (tiny sizes: the tests)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no such fault: {fault!r}")
    x = _embed(params["embed"], jnp.asarray(ids))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(cfg, i, precision, fault)(
            {n: params[f"layers.{i}.{n}"]
             for n in weights_mod.layer_leaves(cfg, i)}, x)
    return _head(_frozen(cfg), precision)(params["final_norm"],
                                          params["lm_head"], x)


def teacher_forced_logits(seed, cfg, sequences, weight_dtype, pad_to,
                          precision="float32", fault=None):
    """For each (prompt, tokens) the logits [len(tokens), V] that predict
    each token, from ONE full causal forward over prompt + tokens.  The
    weights are made layer by layer, in the dtype they are served in, and
    widened to float32; a sequence at a time, padded to whole BUCKETs (at
    most `pad_to`: a cell's 1 k median beside its 6 k tail would cost four
    times the work padded to the longest), so that the scores of one block
    of query rows ([heads, QUERY_BLOCK, S]) are the largest thing beside
    them."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no such fault: {fault!r}")
    logits_of = _head(_frozen(cfg), precision)

    def leaf(name):
        return weights_mod.make_leaf(seed, cfg, name,
                                     weight_dtype).astype(jnp.float32)
    embed = leaf("embed")
    xs = []
    for prompt, tokens in sequences:
        n = len(prompt) + len(tokens)
        ids = np.zeros((1, min(-(-n // BUCKET) * BUCKET, int(pad_to))),
                       np.int32)
        ids[0, :n] = np.concatenate([prompt, tokens])
        xs.append(_embed(embed, jnp.asarray(ids)))
    del embed
    for i in range(cfg["num_hidden_layers"]):
        p = {n: leaf(f"layers.{i}.{n}")
             for n in weights_mod.layer_leaves(cfg, i)}
        xs = [_layer(cfg, i, precision, fault)(p, x) for x in xs]
        del p
    final_norm, lm_head = leaf("final_norm"), leaf("lm_head")
    out = []
    for x, (prompt, tokens) in zip(xs, sequences):
        rows = logits_of(final_norm, lm_head, x)[0]
        out.append(rows[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
    return out
