"""The plain reference of the latent-attention, sparse-expert decoder
(`model_type` `sarvam_mla`): jax.numpy, float32, `Precision.HIGHEST`.

The equations, with x the block's normed input, per head h of nh:

  q = x Wq -> [q_nope | q_rope] (rmsnorm over the head's 192 first: g_q)
  [c | k_r] = x Wkv_a;  c = rmsnorm(c; g_kv);  k_r, q_rope rotated
                        (rotate-half; ONE k_r a token, shared by the heads)
  [k_nope_h | v_h] = c Wkv_b[h]
  score_h = (q_nope_h . k_nope_h + q_rope_h . k_r) * (nope + rope)^-1/2 * m^2
  causal softmax, o_h = sum p v_h, out = concat_h(o_h) Wo

  rotary: DeepSeek-V2's YaRN (`deepseek_yarn`): each frequency blended with
  itself over `factor` by the linear ramp between the correction dims of
  beta_fast and beta_slow; m = 0.1 * mscale_all_dim * ln(factor) + 1.

  s = sigmoid(x Wr) over the router's whole width; the top k of s + b;
  w_e = routed_scaling_factor * s_e / sum of the chosen s;
  y = shared(x) + sum_e w_e expert_e(x), every expert
  down(silu(gate x) * up x); no token dropped.

Expanded attention only, a python loop over the experts HELD here (the
configuration's share: experts `experts_held[0]` .. of `router_width`; what
the absent ones would add is left out, as in the program), no cache, no
batching.  Weights come from `benchmark/weights_mla_moe.py`, layer by layer,
so the most it holds is one layer's float32 experts.  It imports nothing of
`paddle_tpu`.

`precision` picks the arithmetic of the products with weights, as
`decoder_f32.weight_matmul` defines it ("float32" the reference, "int8" the
control one precision below bfloat16).  `fault` plants ONE departure from
the equations, for the readings that `served_token_gap`'s limit is set from
(`benchmark/control_faults.py`); None in every benchmark run.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights_mla_moe as weights_mod
from reference.decoder_f32 import HIGHEST, rms_norm, weight_matmul

FAULTS = ("no_shared_expert", "no_router_bias", "no_renormalisation",
          "no_routed_scaling", "top_k_minus_1", "k_r_unrotated",
          "no_yarn_mscale", "renormalised_over_held")


def yarn_inv_freq(dim, base, scaling):
    factor = scaling["factor"]
    span = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(span / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(plain / factor * ramp + plain * (1 - ramp),
                       jnp.float32)


def yarn_mscale(scaling):
    return 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0


def rope(x, positions, inv_freq):
    """x [S, heads, d] rotated (rotate-half), positions [S]."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(p, h, cfg, mm, fault=None):
    """h [B, S, H] normed -> [B, S, H]."""
    b, s, _ = h.shape
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, r, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    eps, scaling = cfg["rms_norm_eps"], cfg["rope_scaling"]
    inv_freq = yarn_inv_freq(r, cfg["rope_theta"], scaling)
    m = 1.0 if fault == "no_yarn_mscale" else yarn_mscale(scaling)
    scale = (nope + r) ** -0.5 * m * m
    pos = jnp.arange(s)
    q = rms_norm(mm(h, p["q_proj"]).reshape(b, s, nh, nope + r),
                 p["q_norm"], eps)
    kv_a = mm(h, p["kv_a_proj"])
    c = rms_norm(kv_a[..., :rank], p["kv_norm"], eps)
    kv = mm(c, p["kv_b_proj"]).reshape(b, s, nh, nope + vd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = math.gcd(nh, 8)     # heads whose [S, S] scores stand together

    def one(row):
        q_row, k_r, kv_row = row
        q_rope = rope(q_row[..., nope:], pos, inv_freq)
        k_rope = k_r if fault == "k_r_unrotated" \
            else rope(k_r[:, None, :], pos, inv_freq)[:, 0]

        def heads(part):
            q_n, q_r, kv_h = part        # [S, group, ..]
            scores = (jnp.einsum("shd,thd->hst", q_n, kv_h[..., :nope],
                                 precision=HIGHEST)
                      + jnp.einsum("shd,td->hst", q_r, k_rope,
                                   precision=HIGHEST)) * scale
            probs = jax.nn.softmax(
                jnp.where(causal[None], scores, -jnp.inf), -1)
            return jnp.einsum("hst,thd->shd", probs, kv_h[..., nope:],
                              precision=HIGHEST)

        def split(a):                    # [S, nh, d] -> [nh/group, S, group, d]
            return a.reshape(s, nh // group, group, -1).transpose(1, 0, 2, 3)
        o = jax.lax.map(heads, (split(q_row[..., :nope]), split(q_rope),
                                split(kv_row)))
        return o.transpose(1, 0, 2, 3).reshape(s, nh * vd)
    out = jax.lax.map(one, (q, kv_a[..., rank:], kv))
    return mm(out, p["o_proj"])


def swiglu(x, w1, w2, mm):
    """down(silu(gate x) * up x) with gate | up side by side in w1."""
    gu = mm(x, w1)
    half = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], w2)


def routing(x, router, bias, cfg, fault=None):
    """(chosen expert ids [.., k], their weights [.., k]) over the router's
    whole width, in float32 whatever `precision`: the router is not a
    weight product a serving dtype touches."""
    k = cfg["num_experts_per_tok"] - (fault == "top_k_minus_1")
    s = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    pick = s if fault == "no_router_bias" else s + bias
    _, chosen = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, chosen, -1)
    if fault != "no_renormalisation":
        first, count = cfg["experts_held"][0], cfg["num_experts"]
        norm = w
        if fault == "renormalised_over_held":
            norm = jnp.where((chosen >= first) & (chosen < first + count),
                             w, 0.0)
        w = w / jnp.maximum(jnp.sum(norm, -1, keepdims=True), 1e-20)
    if fault != "no_routed_scaling":
        w = w * cfg["routed_scaling_factor"]
    return chosen, w


def expert_layer(p, h, cfg, mm, fault=None):
    """shared(h) + the part of sum_e w_e expert_e(h) that the experts held
    here give."""
    chosen, w = routing(h, p["router"], p["router_bias"], cfg, fault)
    y = jnp.zeros_like(h) if fault == "no_shared_expert" \
        else swiglu(h, p["shared_w1"], p["shared_w2"], mm)
    first = cfg["experts_held"][0]
    for j in range(cfg["num_experts"]):
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), -1)
        y = y + w_j[..., None] * swiglu(h, p["experts_w1"][j],
                                        p["experts_w2"][j], mm)
    return y


def layer(p, x, cfg, precision, dense, fault=None):
    mm = weight_matmul(precision)
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["input_norm"], eps), cfg, mm, fault)
    h = rms_norm(x, p["post_norm"], eps)
    if dense:
        return x + mm(jax.nn.silu(mm(h, p["gate_proj"]))
                      * mm(h, p["up_proj"]), p["down_proj"])
    return x + expert_layer(p, h, cfg, mm, fault=fault)


def head_logits(final_norm, lm_head, x, cfg, precision):
    return weight_matmul(precision)(
        rms_norm(x, final_norm, cfg["rms_norm_eps"]), lm_head)


# the keys of a configuration file the equations read
_KEYS = ("hidden_size", "vocab_size", "num_attention_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "intermediate_size", "moe_intermediate_size", "num_shared_experts",
         "num_experts", "router_width", "experts_held", "num_experts_per_tok",
         "routed_scaling_factor", "rms_norm_eps", "rope_theta", "rope_scaling",
         "first_k_dense_replace", "num_hidden_layers")


def _frozen(cfg):
    """Those keys as a hashable key of the compiled programs."""
    return tuple((k, tuple(sorted(cfg[k].items())) if k == "rope_scaling"
                  else tuple(cfg[k]) if k == "experts_held" else cfg[k])
                 for k in _KEYS)


def _thaw(items):
    return {k: dict(v) if k == "rope_scaling" else v for k, v in items}


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, precision, fault):
    cfg = _thaw(cfg_items)
    dense = jax.jit(lambda p, x: layer(p, x, cfg, precision, True, fault))
    sparse = jax.jit(lambda p, x: layer(p, x, cfg, precision, False, fault))
    logits = jax.jit(lambda n, w, x: head_logits(n, w, x, cfg, precision))
    return dense, sparse, logits


_embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0))


def forward_logits(params, ids, cfg, precision="float32", fault=None):
    """Logits [B, S, V] of one full causal forward; params: {name: float32
    array} of the whole model (tiny sizes: the tests)."""
    dense, sparse, logits_of = _programs(_frozen(cfg), precision, fault)
    x = _embed(params["embed"], jnp.asarray(ids))
    for i in range(cfg["num_hidden_layers"]):
        fwd = dense if i < cfg["first_k_dense_replace"] else sparse
        x = fwd({n: params[f"layers.{i}.{n}"]
                 for n in weights_mod.layer_leaves(cfg, i)}, x)
    return logits_of(params["final_norm"], params["lm_head"], x)


def teacher_forced_logits(seed, cfg, sequences, weight_dtype, pad_to,
                          precision="float32", fault=None):
    """For each (prompt, tokens) the logits [len(tokens), V] that predict
    each token, from ONE full causal forward over prompt + tokens.  The
    weights are made layer by layer, in the dtype they are served in, and
    widened to float32; a row of the batch at a time, so that the scores
    of one sequence ([heads, S, S]) are the largest thing beside them."""
    dense, sparse, logits_of = _programs(_frozen(cfg), precision, fault)

    def leaf(name):
        return weights_mod.make_leaf(seed, cfg, name,
                                     weight_dtype).astype(jnp.float32)
    ids = np.zeros((len(sequences), pad_to), np.int32)
    for r, (prompt, tokens) in enumerate(sequences):
        ids[r, :len(prompt)] = prompt
        ids[r, len(prompt):len(prompt) + len(tokens)] = tokens
    x = _embed(leaf("embed"), jnp.asarray(ids))
    for i in range(cfg["num_hidden_layers"]):
        fwd = dense if i < cfg["first_k_dense_replace"] else sparse
        p = {n: leaf(f"layers.{i}.{n}")
             for n in weights_mod.layer_leaves(cfg, i)}
        x = jnp.concatenate([fwd(p, x[r:r + 1])
                             for r in range(len(sequences))])
        del p
    final_norm, lm_head = leaf("final_norm"), leaf("lm_head")
    out = []
    for r, (prompt, tokens) in enumerate(sequences):
        rows = logits_of(final_norm, lm_head, x[r:r + 1])[0]
        out.append(rows[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
    return out
