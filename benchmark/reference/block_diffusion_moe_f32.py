"""The plain reference of the block-diffusion sparse-expert decoder
(`model_type` `sdar_moe`, SDAR-30B-A3B-Chat): jax.numpy, float32,
`Precision.HIGHEST`.

The equations, with x a layer's input, L the block length, positions from 0:

  h = rmsnorm(x);  q = h Wq as nh heads of d;  k = h Wk, v = h Wv as nkv
  heads of d;  q <- rmsnorm_d(q; g_q), k <- rmsnorm_d(k; g_k) per head,
  BEFORE the rotary;  q, k rotated (rotate-half, theta);  scores
  q k^T / sqrt(d), each kv head serving nh / nkv query heads;
  THE MASK IS BLOCK-CAUSAL: query position i sees key position j iff
  j // L <= i // L (its whole block, both ways, and every earlier block);
  x <- x + softmax(scores) v Wo

  h = rmsnorm(x);  p = softmax(h Wr) over ALL experts in float32;  the top k
  of p;  w_e = p_e / sum of the chosen p (`norm_topk_prob`);
  x <- x + sum_e w_e down_e(silu(gate_e h) * up_e h);  no token dropped, no
  shared expert, no bias, no scaling factor

  logits = rmsnorm(x) W_head, and THE LOGIT AT POSITION i PREDICTS THE TOKEN
  AT POSITION i (the masked position itself; no next-token shift).

Generation (SDAR `generate.py`, `block_diffusion_generate`; S denoising
steps, greedy, `low_confidence_dynamic` with a confidence threshold): blocks
are aligned at position 0.  The prompt's whole blocks are committed as they
are.  The first generated block holds the prompt's last `P mod L` tokens and
[MASK] in the rest; every later one is all [MASK].  For a block, repeat: if
no lane is masked, COMMIT the block (the forward whose K/V a cache keeps) and
go on; else one DENOISE forward of the committed prefix + the block gives in
every masked lane x0 = argmax logits and its confidence c = softmax(logits)
[x0], and unmasks all masked lanes with c > threshold if they number at
least this step's quota (L // S, the first L mod S steps one more), else the
quota's lanes of highest confidence.

`forward_logits` is (a): one sequence under the block mask, a loop over the
experts one at a time, no cache, no batching.  `generate` is (b): each pass a
FULL forward of prefix + block (in one padded sequence: what lies in later
blocks is invisible).  `replayed_logits` is (c): what decides a served
request: the logits of every denoise pass of every block under the state the
served run had at that pass, layer by layer: the committed sequence's rows
under the block mask (their K/V are what the commit passes cached) beside
each (block, pass) state's L rows, which see the committed rows of the
earlier blocks and their own block.  All three are ONE computation: rows at
positions with a matrix of who sees whom.

Departures from the published code, each without effect on what is computed
here: (1) a lane is masked by a FLAG the schedule keeps, not by `id ==
mask_id` (the traffic draws ids over the whole vocabulary, so a prompt may
hold the mask id); (2) rotate-half rotary layout (weights are random: the
interleaved layout is a column permutation of Wq / Wk); (3) where fewer
lanes are masked than a step's quota (unreachable while L / S is whole), the
masked ones are taken and no unmasked lane is rewritten (`torch.topk` over
-inf would pick one); (4) confidence ties go to the lower lane.

Weights come from `benchmark/weights_sdar_moe.py`, layer by layer, so the
most it holds is one layer's float32 experts.  It imports nothing of
`paddle_tpu`.  `precision` picks the arithmetic of the products with weights
(`decoder_f32.weight_matmul`: "float32" the reference, "int8" the control
one precision below bfloat16); `fault` plants ONE departure from the
equations, for the readings the limits of `correct` are set from
(`benchmark/control_diffusion.py`); None in every benchmark run.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights_sdar_moe as weights_mod
from reference.decoder_f32 import HIGHEST, rms_norm, rope, weight_matmul

FAULTS = ("causal_in_block", "next_token_shift", "no_commit_pass",
          "no_qk_norm", "no_renormalisation", "top_k_minus_1",
          "rope_theta_1e4")


# -- one layer over rows -----------------------------------------------------

def attention(p, h, pos, visible, cfg, mm, fault=None):
    """h [N, H] normed rows at positions pos [N]; visible [N, N]: row i
    sees row j.  One kv head's group of query heads at a time."""
    n = h.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    theta = 1e4 if fault == "rope_theta_1e4" else cfg["rope_theta"]
    q = mm(h, p["q_proj"]).reshape(n, nh, d)
    k = mm(h, p["k_proj"]).reshape(n, nkv, d)
    v = mm(h, p["v_proj"]).reshape(n, nkv, d)
    if fault != "no_qk_norm":
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)

    def group(part):
        q_g, k_h, v_h = part                  # [N, g, d], [N, d], [N, d]
        scores = jnp.einsum("sgd,td->gst", q_g, k_h,
                            precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("gst,td->sgd", probs, v_h, precision=HIGHEST)
    out = jax.lax.map(group, (
        q.reshape(n, nkv, nh // nkv, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))     # [nkv, N, g, d]
    return mm(out.transpose(1, 0, 2, 3).reshape(n, nh * d), p["o_proj"])


def routing(x, router, cfg, fault=None):
    """(chosen expert ids [N, k], their weights [N, k]) in float32 whatever
    `precision`: the router is not a weight product a serving dtype
    touches."""
    k = cfg["num_experts_per_tok"] - (fault == "top_k_minus_1")
    probs = jax.nn.softmax(jnp.matmul(x, router, precision=HIGHEST), -1)
    w, chosen = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"] and fault != "no_renormalisation":
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w


def expert_layer(p, h, cfg, mm, fault=None):
    """sum_e w_e down_e(silu(gate_e h) * up_e h), gate | up side by side in
    an expert's first matrix: a loop over the experts, one at a time, each
    over every row, weighted (`fori_loop`: the loop's body is compiled once,
    not once an expert)."""
    chosen, w = routing(h, p["router"], cfg, fault)

    def add_expert(j, y):
        w_j = jnp.sum(jnp.where(chosen == j, w, 0.0), -1)
        gu = mm(h, p["experts_w1"][j])
        half = gu.shape[-1] // 2
        return y + w_j[:, None] * mm(
            jax.nn.silu(gu[:, :half]) * gu[:, half:], p["experts_w2"][j])
    return jax.lax.fori_loop(0, cfg["num_experts"], add_expert,
                             jnp.zeros_like(h))


def layer(p, x, pos, visible, cfg, precision, fault=None):
    mm = weight_matmul(precision)
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["input_norm"], eps), pos, visible,
                      cfg, mm, fault)
    return x + expert_layer(p, rms_norm(x, p["post_norm"], eps), cfg, mm,
                            fault)


def head_logits(final_norm, lm_head, x, cfg, precision):
    return weight_matmul(precision)(
        rms_norm(x, final_norm, cfg["rms_norm_eps"]), lm_head)


# the keys of a configuration file the equations read
_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "moe_intermediate_size",
         "num_experts", "num_experts_per_tok", "norm_topk_prob",
         "rms_norm_eps", "rope_theta", "num_hidden_layers", "block_length",
         "denoising_steps", "mask_token_id", "confidence_threshold")


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, precision, fault):
    cfg = dict(cfg_items)
    return (jax.jit(lambda p, x, pos, visible: layer(
                p, x, pos, visible, cfg, precision, fault)),
            jax.jit(lambda n, w, x: head_logits(n, w, x, cfg, precision)))


def _compiled(cfg, precision, fault):
    return _programs(tuple((k, cfg[k]) for k in _KEYS), precision, fault)


_embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0))


def block_visible(n, block_length, causal=False):
    """[n, n] bool: position i sees position j under the block mask (or,
    the fault, the causal one)."""
    at = np.arange(n)
    if causal:
        return at[None, :] <= at[:, None]
    return at[None, :] // block_length <= at[:, None] // block_length


# -- (a) the full forward ----------------------------------------------------

def forward_logits(params, ids, cfg, precision="float32", fault=None):
    """Logits [S, V] of ONE sequence under the block mask, row i predicting
    the token AT position i; params: {name: float32 array} of the whole
    model (tiny sizes: the tests)."""
    layer_of, logits_of = _compiled(cfg, precision, fault)
    ids = np.asarray(ids, np.int32)
    pos = jnp.arange(len(ids))
    visible = jnp.asarray(block_visible(len(ids), cfg["block_length"],
                                        fault == "causal_in_block"))
    x = _embed(params["embed"], jnp.asarray(ids))
    for i in range(cfg["num_hidden_layers"]):
        x = layer_of({n: params[f"layers.{i}.{n}"]
                      for n in weights_mod.LAYER_LEAVES}, x, pos, visible)
    logits = logits_of(params["final_norm"], params["lm_head"], x)
    if fault == "next_token_shift":
        logits = jnp.concatenate([logits[:1], logits[:-1]])
    return logits


# -- (b) generation ----------------------------------------------------------

def step_quotas(cfg):
    """Lanes a denoising step must fix at least: L // S, the first L mod S
    steps one more (`get_num_transfer_tokens`)."""
    L, S = cfg["block_length"], cfg["denoising_steps"]
    return [L // S + (s < L % S) for s in range(S)]


def unmask_choice(conf, masked, quota, threshold):
    """[L] bool: the lanes a denoise pass fixes, from each lane's confidence
    and whether it is still masked."""
    conf = np.where(masked, conf, -np.inf)
    high = conf > threshold
    if high.sum() >= quota:
        return high
    order = np.argsort(-conf, kind="stable")[:quota]
    chosen = np.zeros(len(conf), bool)
    chosen[order] = True
    return chosen & masked


def confidence(logits):
    """(argmax token, its softmax probability) of float32 logits [.., V]."""
    top = logits.max(-1)
    return logits.argmax(-1), 1.0 / np.exp(logits - top[..., None]).sum(-1)


def generate(params, prompt, max_new_tokens, cfg, pad_to=None):
    """SDAR's loop over one prompt.  Returns {"tokens", "passes": the
    denoising step of its block at which each token was fixed, both cut at
    max_new_tokens; "pass_logits": [(block, step, logits [L, V])] of every
    denoise pass}."""
    L, S = cfg["block_length"], cfg["denoising_steps"]
    mask_id, threshold = cfg["mask_token_id"], cfg["confidence_threshold"]
    quotas = step_quotas(cfg)
    prompt = np.asarray(prompt, np.int32)
    P = len(prompt)
    blocks = -(-(P + max_new_tokens) // L)
    seq = np.zeros(pad_to or blocks * L, np.int32)
    seq[:P] = prompt
    tokens, passes, pass_logits = [], [], []
    for b in range(P // L, blocks):
        lo = b * L
        given = lo + np.arange(L) < P
        block = np.where(given, seq[lo:lo + L], mask_id).astype(np.int32)
        masked, fixed_at = ~given, np.where(given, -1, 0)
        for step in range(S + 1):
            if not masked.any():
                break           # the commit pass: the block as it stands
            seq[lo:lo + L] = block
            logits = np.asarray(forward_logits(params, seq, cfg)[lo:lo + L])
            pass_logits.append((b, step, logits))
            x0, conf = confidence(logits)
            fix = unmask_choice(conf, masked, quotas[step], threshold)
            block = np.where(fix, x0, block).astype(np.int32)
            fixed_at = np.where(fix, step, fixed_at)
            masked = masked & ~fix
        assert not masked.any()
        seq[lo:lo + L] = block
        tokens.extend(block[~given].tolist())
        passes.extend(fixed_at[~given].tolist())
    return {"tokens": np.asarray(tokens[:max_new_tokens], np.int32),
            "passes": np.asarray(passes[:max_new_tokens], np.int32),
            "pass_logits": pass_logits}


# -- (c) a served request, replayed ------------------------------------------

def replay_plan(prompt, tokens, passes, cfg, pad_to, fault=None):
    """What a served request's denoise passes looked like, as rows for
    `layer`: the committed sequence (prompt + tokens, whole blocks only: a
    last block cut by max_new_tokens had lanes nobody delivered) on rows
    0..pad_to, then L rows for each (block, pass) state.  Returns {"states":
    [(block, pass)], "masked" / "fixed" [n, L] bool: the lanes masked AT a
    pass and those the run fixed IN it, "token" [n, L]: the token the run
    ended with in that lane, and the rows' "ids", "pos", "visible"}."""
    L, mask_id = cfg["block_length"], cfg["mask_token_id"]
    P = len(prompt)
    T = (P + len(tokens)) // L * L
    if T > pad_to:
        raise ValueError(f"{T} committed rows do not fit pad_to {pad_to}")
    seq = np.concatenate([prompt, tokens]).astype(np.int32)[:T]
    fixed_at = np.concatenate([np.full(P, -1), passes])[:T].reshape(-1, L)
    states = [(b, p) for b in range(P // L, T // L)
              for p in range(fixed_at[b].max() + 1)]
    n = len(states)
    pad_states = -(-max(n, 1) // 16) * 16
    N = pad_to + pad_states * L
    ids = np.zeros(N, np.int32)
    ids[:T] = seq
    pos = np.zeros(N, np.int32)
    pos[:pad_to] = np.arange(pad_to)
    causal = fault == "causal_in_block"
    visible = np.eye(N, dtype=bool)          # a padded row sees itself
    visible[:pad_to, :pad_to] = block_visible(pad_to, L, causal)
    own = block_visible(L, L, causal)
    masked = np.zeros((n, L), bool)
    for s, (b, p) in enumerate(states):
        rows = slice(pad_to + s * L, pad_to + (s + 1) * L)
        masked[s] = fixed_at[b] >= p
        ids[rows] = np.where(masked[s], mask_id, seq[b * L:(b + 1) * L])
        pos[rows] = b * L + np.arange(L)
        visible[rows, :b * L] = True
        visible[rows, rows] = own
    block_of = np.concatenate([np.arange(pad_to) // L,
                               [b for b, _ in states for _ in range(L)],
                               np.full((pad_states - n) * L, -1)])
    if fault == "no_commit_pass":
        # what a later block finds cached of block b is what b's LAST
        # denoise pass wrote, the lanes it fixed still [MASK]
        for s, (b, p) in enumerate(states):
            if s + 1 < n and states[s + 1][0] == b:
                continue
            later = block_of > b
            last = slice(pad_to + s * L, pad_to + (s + 1) * L)
            visible[later, last] = True
            visible[later, b * L:(b + 1) * L] = False
    token = np.stack([seq[b * L:(b + 1) * L] for b, _ in states]) \
        if n else np.zeros((0, L), np.int32)
    fixed = np.stack([fixed_at[b] == p for b, p in states]) \
        if n else np.zeros((0, L), bool)
    return {"states": states, "masked": masked, "fixed": fixed,
            "token": token, "ids": ids, "pos": pos, "visible": visible}


def replayed_logits(seed, cfg, sample, weight_dtype, pad_to,
                    precision="float32", fault=None):
    """For each served (prompt, tokens, passes) of `sample`, in turn:
    (its replay_plan, logits [n states, L, V] of its denoise passes).  A
    generator: the rows of all requests go through the layers together
    (weights made layer by layer, in the dtype they are served in, widened
    to float32), then the head a request at a time, so that one request's
    logits are the most that stands beside the head."""
    layer_of, logits_of = _compiled(cfg, precision, fault)
    L = cfg["block_length"]

    def leaf(name):
        return weights_mod.make_leaf(seed, cfg, name,
                                     weight_dtype).astype(jnp.float32)
    plans = [replay_plan(np.asarray(prompt), np.asarray(tokens),
                         np.asarray(passes), cfg, pad_to, fault)
             for prompt, tokens, passes in sample]
    embed = leaf("embed")
    rows = [(_embed(embed, jnp.asarray(plan["ids"])),
             jnp.asarray(plan["pos"]), jnp.asarray(plan["visible"]))
            for plan in plans]
    del embed
    for i in range(cfg["num_hidden_layers"]):
        p = {n: leaf(f"layers.{i}.{n}") for n in weights_mod.LAYER_LEAVES}
        rows = [(layer_of(p, x, pos, visible), pos, visible)
                for x, pos, visible in rows]
        del p
    final_norm, lm_head = leaf("final_norm"), leaf("lm_head")
    for plan, (x, _, _) in zip(plans, rows):
        n = len(plan["states"])
        hidden = x[pad_to:pad_to + n * L].reshape(n, L, -1)
        if fault == "next_token_shift" and n:
            # lane i read from the row before it; lane 0 from the last
            # committed row of the block before (its own in block 0)
            before = jnp.stack([x[b * L - 1] if b else hidden[s, 0]
                                for s, (b, _) in enumerate(plan["states"])])
            hidden = jnp.concatenate([before[:, None], hidden[:, :-1]], 1)
        yield plan, logits_of(final_norm, lm_head, hidden)
