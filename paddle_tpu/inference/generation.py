"""KV-cached autoregressive generation — the serving decode path.

Reference: `python/paddle/incubate/nn/functional/
block_multihead_attention.py` (paged-KV decode attention) and
paddlenlp's GenerationMixin.generate.

TPU-native design: the ENTIRE generation — prefill over the prompt plus
a `lax.scan` over max_new_tokens decode steps — is ONE jitted program.
A per-token host loop would pay a host dispatch per token (its cost on
today's chip: not measured); the scanned program pays it once.  The KV
cache
is a static-shape fixed-size buffer per layer sized to
prompt+max_new_tokens (XLA requires static shapes; "paged" blocks buy
nothing on TPU where the compiler owns layout), and
decode attention is one batched masked GEMV (ops.cached_attention — a
Pallas q_len==1 kernel would be grid-overhead-bound, see
ops/pallas/flash_attention.py packed-path notes).
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..framework import random as prandom

__all__ = ["generate"]


def _sample(logits, key, temperature, top_p, top_k):
    """Next-token sampling on [b, V] fp32 logits."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:                       # greedy
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None:
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_l = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs <= top_p               # always keeps top-1
        sorted_l = jnp.where(keep, sorted_l, -1e30)
        inv = jnp.argsort(sort_idx, axis=-1)
        logits = jnp.take_along_axis(sorted_l, inv, axis=-1)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _kv_layout_fingerprint():
    """The process-global KV-layout + decode-precision config a
    compiled program may have baked in: (kv_cache_dtype, kv_page_size,
    kv_pool_pages, weight_only_dtype, weight_only_group_size).
    Appended to every _model_program_cache key so toggling
    FLAGS_kv_cache_dtype, the pool geometry or
    FLAGS_weight_only_dtype mid-process can never replay a stale
    program built against the previous layout (a paged-pool program
    quantizing into a pool that no longer exists — or an fp program
    fed packed int8 weights — would silently corrupt serving).
    Deliberately blanket (the ISSUE 7/11 contract): programs that do
    not bake the KV layout pay a spurious rebuild on a flag flip —
    rare, and strictly safer than whitelisting which key tags are
    layout-dependent and forgetting one later."""
    from ..framework.flags import get_flag
    return ("kvcfg", str(get_flag("kv_cache_dtype", "auto")),
            int(get_flag("kv_page_size", 16)),
            int(get_flag("kv_pool_pages", 0)),
            str(get_flag("weight_only_dtype", "none")),
            int(get_flag("weight_only_group_size", 64)))


def _model_quant_fingerprint(model):
    """The MODEL-side half of the weight-only fingerprint: whether
    quantization.weight_only.quantize_model has packed this model's
    weights (and at what config).  Per-model state, not a flag — an
    explicitly quantized model under flags-off defaults must still
    miss every program traced against its fp weights (the packed
    state_dict carries extra scale entries, so a stale replay would
    zip-misalign the swapped parameters)."""
    wo = getattr(model, "_weight_only", None)
    if wo is None:
        return ("wo", "none")
    return ("wo", wo["dtype"], wo["group_size"])


def _store_key(model, key):
    """The key _model_program_cache actually stores under: the
    caller's key plus the KV-layout/flag fingerprint plus the model's
    quantization fingerprint.  The SINGLE place the composition
    lives — membership probes go through _program_cache_contains,
    never hand-built keys."""
    return (tuple(key) if isinstance(key, (tuple, list)) else (key,)) \
        + (_kv_layout_fingerprint(), _model_quant_fingerprint(model))


def _program_cache_contains(model, key) -> bool:
    """Would _model_program_cache(model, key, ...) hit, under the
    CURRENT KV-layout flags and the model's quantization state?
    (The serving batcher's first-use probe.)"""
    return _store_key(model, key) in model.__dict__.get("_gen_compiled",
                                                        {})


def _model_program_cache(model, key, build, cap=16):
    """Compiled-program cache living ON the model object, so its
    lifetime (and the closed-over weights) ends with the model —
    a global registry would pin every served model's HBM forever.
    Shared by generate() and the serving ContinuousBatcher (whose two
    step programs thereby survive across batcher instances).  Capped
    LRU (hits refresh recency): the batcher's step programs run every
    chunk, so generate() shape churn evicts cold generate entries
    rather than the serving hot path — FIFO would evict the
    earliest-inserted (hottest) programs first.  Keys carry the
    KV-layout fingerprint (see _kv_layout_fingerprint); callers keep
    their key[0] tag — the fingerprint is appended, not prepended."""
    key = _store_key(model, key)
    store = model.__dict__.setdefault("_gen_compiled", {})
    fn = store.pop(key, None)
    if fn is None:
        # announce the cache miss to the analysis layer: an active
        # recompile_guard records it in .cache_builds, so tests bound
        # program-cache growth the same way they bound XLA compiles
        from ..analysis.lints import note_program_build
        note_program_build(key)
        fn = build()
        if len(store) >= cap:
            store.pop(next(iter(store)))
    store[key] = fn                    # (re)insert at the recent end
    return fn


def _compiled_gen(model, b, s_prompt, max_new, temperature, top_p,
                  top_k, eos_token_id, max_len):
    cache_key = (b, s_prompt, max_new, temperature, top_p, top_k,
                 eos_token_id, max_len)

    def build():
        # closure construction (state_dict walk included) only happens
        # on a cache MISS — the warm-path cost is the dict lookup
        from ..jit import _swapped_state
        sd = model.state_dict()
        names = list(sd.keys())

        def gen(param_vals, ids, key):
            with _swapped_state(model, names, list(param_vals)):
                cache = model.init_cache(b, max_len)
                logits, cache = model.forward_cached(
                    ids, cache, jnp.asarray(0, jnp.int32))
                key, sub = jax.random.split(key)
                first = _sample(logits[:, -1], sub, temperature, top_p,
                                top_k)
                done0 = jnp.zeros((b,), bool) if eos_token_id is None \
                    else (first == eos_token_id)

                def body(carry, _):
                    cache, tok, pos, key, done = carry
                    lg, cache = model.forward_cached(tok[:, None],
                                                     cache, pos)
                    key, sub = jax.random.split(key)
                    nxt = _sample(lg[:, 0], sub, temperature, top_p,
                                  top_k)
                    if eos_token_id is not None:
                        nxt = jnp.where(done, eos_token_id, nxt)
                        done = done | (nxt == eos_token_id)
                    return (cache, nxt, pos + 1, key, done), nxt

                init = (cache, first, jnp.asarray(s_prompt, jnp.int32),
                        key, done0)
                _, rest = jax.lax.scan(body, init, None,
                                       length=max_new - 1)
            return jnp.concatenate([first[:, None], rest.T], axis=1)

        return jax.jit(gen)

    return _model_program_cache(model, cache_key, build)


def generate(model, input_ids, max_new_tokens: int = 32,
             temperature: float = 0.0, top_p: Optional[float] = None,
             top_k: Optional[int] = None,
             eos_token_id: Optional[int] = None,
             max_length: Optional[int] = None, seed: Optional[int] = None
             ) -> Tensor:
    """Generate [b, max_new_tokens] token ids.  temperature=0 → greedy.

    The compiled program is cached per (model, shape, sampling config);
    repeat calls with the same prompt shape reuse it."""
    if getattr(model, "block_diffusion", lambda: None)() is not None:
        raise NotImplementedError(
            "this loop decodes one token a step; a model that generates "
            "by diffusion over blocks is served by "
            "inference.ContinuousBatcher")
    ids = input_ids.value if isinstance(input_ids, Tensor) \
        else jnp.asarray(np.asarray(input_ids))
    ids = ids.astype(jnp.int32)
    b, s = int(ids.shape[0]), int(ids.shape[1])
    max_len = int(max_length or (s + max_new_tokens))
    if s + int(max_new_tokens) > max_len:
        raise ValueError(
            f"max_length={max_len} cannot hold prompt ({s}) + "
            f"{max_new_tokens} new tokens — the cache is a fixed-size "
            "buffer (no wraparound); raise max_length")
    fn = _compiled_gen(model, b, s, int(max_new_tokens),
                       float(temperature),
                       None if top_p is None else float(top_p),
                       None if top_k is None else int(top_k),
                       eos_token_id, max_len)
    sd = model.state_dict()
    param_vals = [sd[n]._value for n in sd.keys()]
    key = jax.random.PRNGKey(seed) if seed is not None \
        else prandom.next_key()
    out = fn(param_vals, ids, key)
    return Tensor(out, stop_gradient=True)
