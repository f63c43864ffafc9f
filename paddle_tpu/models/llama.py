"""Llama family — the flagship model (baseline config 3: Llama-2 7B/13B
sharding-stage3 pretraining, SURVEY §6 / BASELINE.json).

Reference capability: PaddleNLP-style llama built on the reference's fused
ops (fused_rms_norm, fused_rotary_position_embedding, swiglu,
flash_attention — python/paddle/incubate/nn/functional/) and Fleet TP
layers (mp_layers.py).

TPU-native design:
  - weights created directly in bfloat16 (params + activations); master
    fp32 copies live in the optimizer (multi_precision), matching the
    reference's O2 scheme.
  - attention → paddle_tpu.ops.attention (Pallas flash kernel on TPU).
  - rmsnorm/rope/swiglu → paddle_tpu.ops (Pallas / XLA-fused).
  - TP: q/k/v/gate/up projections are column-sharded, o/down row-sharded
    over the 'mp' mesh axis; embedding vocab-sharded.  Sharding is carried
    by parameter NamedShardings (fleet.meta_parallel), with GSPMD
    inserting collectives — no comm code in the model.
  - sequence axis can additionally be sharded over 'sep' (context
    parallel); ring attention kernel handles the halo exchange.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from .. import tensor as pten
from ..nn import functional as F
from ..framework.tensor import Tensor
from ..framework.dispatch import run, to_tensor_args
from .. import ops as tpu_ops

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "llama_tiny_config", "llama_7b_config",
           "llama_moe_tiny_config", "EarlyExitDraft"]


def _wo_mm(layer, name, x):
    """`x @ W` for the DECODE path, riding the weight-only packed
    representation when quantization.weight_only.quantize_model
    installed one on `layer` (ISSUE 11): the packed weight + its
    `<name>_scale` sibling dispatch to ops.quant_matmul (in-VMEM
    dequant fused into the matmul on TPU, bit-exact jnp twin
    elsewhere).  Unquantized layers take the exact pre-existing
    `x @ w.astype(x.dtype)` — byte-identical flags-off programs."""
    w = getattr(layer, name).value
    wo = getattr(layer, "_wo_dtype", None)
    if wo is None:
        return x @ w.astype(x.dtype)
    scale = getattr(layer, name + "_scale").value
    return tpu_ops.quant_matmul(x, w, scale, wo, layer._wo_group)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # storage dtype of the parameters; None = same as compute dtype.
    # "float32" params + bfloat16 compute is the TPU-idiomatic mixed
    # precision scheme (flax param_dtype/dtype split): the fp32 value IS
    # the master weight — casts fuse into the matmuls, so no separate
    # master copy lives in the optimizer (reference O2 keeps bf16 params
    # + fp32 masters; same math, one less resident copy of the model)
    param_dtype: str | None = None
    use_flash_attention: bool = True
    recompute: bool = False
    # checkpoint only the first N layers (None = all); lets memory-bound
    # configs trade remat flops for activation memory per layer
    recompute_layers: int | None = None
    # "full": save only layer boundaries, replay the whole block.
    # "selective": save post-rope q/k/v, the pre-o-proj attention output
    # and the post-attention residual; the backward replays only the MLP
    # matmuls + the flash-attn forward (reference recompute_granularity)
    recompute_granularity: str = "full"
    # sparse-MoE decoder (reference: fused_moe / Mixtral-style models):
    # >0 replaces each block's dense MLP with moe_num_experts swiglu
    # experts behind a top-k gate; expert dim shards over the mesh's
    # expert axis (MoELayer ep_axis)
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_gate: str = "gshard"
    moe_aux_weight: float = 0.01
    # moe_gate "sigmoid" (DeepSeek-V3 style): fp32 sigmoid scores over
    # `moe_router_width` experts, of which this model HOLDS
    # moe_num_experts starting at moe_first_expert (its share of an
    # expert-parallel deployment; 0 width = all held), top-k of score +
    # bias, weights moe_routed_scaling * s / sum of the chosen s, beside
    # moe_shared_experts always-on experts; dropless.  Experts are
    # moe_intermediate_size wide (0 = intermediate_size); the first
    # first_k_dense_replace layers keep the dense MLP.
    moe_router_width: int = 0
    moe_first_expert: int = 0
    moe_intermediate_size: int = 0
    moe_shared_experts: int = 0
    moe_routed_scaling: float = 1.0
    moe_router_bias: bool = False
    first_k_dense_replace: int = 0
    # latent attention (MLA, DeepSeek-V2): kv_lora_rank > 0 swaps the
    # attention class.  The cache holds ONE row of kv_lora_rank +
    # qk_rope_head_dim a token a layer; heads are qk_nope + qk_rope wide
    # on the query side and v_head_dim on the value side.  use_qk_norm:
    # a learned rmsnorm over each query head before the rotary (the key
    # side's norm is the latent's own).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # In LlamaAttention (no latent): a learned rmsnorm over each query
    # and each key head, head_dim wide, before the rotary (Qwen3's).
    use_qk_norm: bool = False
    # a config's rope_scaling group ({"type": "deepseek_yarn", ...})
    rope_scaling: dict | None = None
    # the size of an attention head where the configuration states one
    # (`head_dim` in its file), else None: read it as `attn_head_dim`,
    # which then derives hidden_size // num_attention_heads.  (MLA heads
    # have their own three sizes.)
    head_dim: int | None = None
    # moe_gate "naive": a dropless softmax router (fp32 softmax over all
    # experts, top-k, weights renormalised over the chosen).  Expert
    # biases are the MoELayer's own default; False for a model whose
    # experts have none (only the dropless gates can do without)
    moe_expert_bias: bool = True
    # How the model GENERATES.  block_length 1: autoregressive, one
    # token a step, causal attention.  block_length L > 1: by diffusion
    # over blocks of L tokens (SDAR): attention is block-causal (query i
    # sees key j iff j // L <= i // L, blocks aligned at position 0),
    # the logit at position i predicts the token AT position i, and a
    # block of [MASK] (mask_token_id) is filled in at most
    # denoising_steps passes: every pass fixes the masked lanes whose
    # confidence (softmax probability of the argmax) is above
    # confidence_threshold if they number at least the pass's quota
    # (block_length / denoising_steps), else the quota's most confident.
    # ContinuousBatcher runs that schedule (block_diffusion()).
    block_length: int = 1
    denoising_steps: int = 1
    mask_token_id: int | None = None
    confidence_threshold: float = 0.9
    # KINDS of attention layer.  layer_types names each layer's kind,
    # "full_attention" or "sliding_attention" (None: every layer full);
    # a sliding layer's lane at position i sees rows j <= i with
    # i - j < sliding_window (the token itself and the sliding_window - 1
    # before it).  rope_layer_types: the kinds whose q and k take the
    # rotary (None: every kind; EXAONE's hybrid models rotate in the
    # sliding layers only).  In the paged pool a sliding layer keeps a
    # RING of O(sliding_window) rows a slot, a full layer the slot's
    # whole depth (kv_row_spec, init_paged_cache).
    layer_types: tuple | None = None
    sliding_window: int = 0
    rope_layer_types: tuple | None = None
    # STATE-SPACE layers (Mamba-2, GraniteMoeHybrid's): a layer whose kind
    # in layer_types is "mamba" mixes with LlamaMamba2Mixer in place of
    # attention.  mamba_n_heads heads of mamba_d_head, a state of
    # mamba_d_state a head row, one group of B and C, a causal depthwise
    # conv of mamba_d_conv taps with a bias.  In the paged cache such a
    # layer keeps a STATE a slot (kv_row_spec's "state" kind), not rows.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    # the dtype a slot's SSM state is STORED in between steps (the scan
    # computes in fp32 either way)
    mamba_state_dtype: str = "float32"
    # GraniteMoeHybrid's multipliers, None where a model has none (then
    # they add no operation to any program): the embedding's scale, the
    # scale of every sublayer's output into the residual stream, the
    # softmax scale of attention (in place of head_dim ** -0.5) and the
    # divisor of the logits
    embedding_multiplier: float | None = None
    residual_multiplier: float | None = None
    attention_multiplier: float | None = None
    logits_scaling: float | None = None

    @property
    def latent_attention(self):
        return self.kv_lora_rank > 0

    def layer_window(self, layer_idx: int) -> int:
        """The window of layer `layer_idx`'s attention, 0 for a full
        layer; the kinds are checked here, where every reader passes."""
        if self.layer_types is None:
            return 0
        kinds = tuple(self.layer_types)
        if len(kinds) != self.num_hidden_layers or set(kinds) - {
                "full_attention", "sliding_attention", "mamba"}:
            raise ValueError(
                f"layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))}; the model has "
                f"{self.num_hidden_layers}, each full_attention, "
                "sliding_attention or mamba")
        if kinds[layer_idx] in ("full_attention", "mamba"):
            return 0
        if self.sliding_window < 1:
            raise ValueError("a sliding_attention layer needs "
                             "sliding_window >= 1")
        if self.latent_attention or self.block_length > 1:
            raise ValueError(
                "sliding_attention layers are LlamaAttention's under the "
                "causal mask: latent (MLA) rows and the block-causal mask "
                "have no window")
        return int(self.sliding_window)

    def layer_rotary(self, layer_idx: int) -> bool:
        if self.rope_layer_types is None:
            return True
        kind = "sliding_attention" if self.layer_window(layer_idx) \
            else "full_attention"
        return kind in tuple(self.rope_layer_types)

    def window_layers(self):
        """Indices of the sliding-window layers, () without any."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_window(i))

    def state_layers(self):
        """Indices of the state-space (mamba) layers, () without any."""
        if self.layer_types is None or "mamba" not in self.layer_types:
            return ()
        self.layer_window(0)                 # the kinds' check
        if self.latent_attention or self.block_length > 1 \
                or self.window_layers():
            raise ValueError(
                "mamba layers stand beside full LlamaAttention layers of "
                "an autoregressive model")
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "mamba")

    @property
    def mamba_inner(self) -> int:
        """Width of a mamba layer's x (and z): heads x head size."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the conv: x, B and C side by side."""
        return self.mamba_inner + 2 * self.mamba_d_state

    def block_diffusion(self):
        """None for an autoregressive model, else how it generates:
        {"block_length", "denoising_steps", "mask_token_id",
        "confidence_threshold"}, checked."""
        L, S = int(self.block_length), int(self.denoising_steps)
        if L <= 1:
            return None
        if self.mask_token_id is None \
                or not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"block_length {L} needs a mask_token_id inside the "
                f"vocabulary (got {self.mask_token_id})")
        if not 1 <= S <= L:
            raise ValueError(f"denoising_steps {S} not in 1..{L} "
                             "(every pass fixes at least one lane)")
        return {"block_length": L, "denoising_steps": S,
                "mask_token_id": int(self.mask_token_id),
                "confidence_threshold": float(self.confidence_threshold)}

    def expert_layer(self, layer_idx: int) -> bool:
        return self.moe_num_experts > 0 \
            and layer_idx >= self.first_k_dense_replace

    @property
    def attn_head_dim(self) -> int:
        """An attention head's size: explicit (`head_dim`) or derived,
        at the time it is read; a copy (`dataclasses.replace`) of a
        config that states none still derives."""
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def storage_dtype(self):
        pd = self.param_dtype or self.dtype
        return jnp.bfloat16 if pd == "bfloat16" else jnp.float32


def llama_tiny_config(**kw):
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=384, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=256)
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def llama_moe_tiny_config(**kw):
    """Tiny sparse-MoE llama (Mixtral-style: swiglu experts, top-2
    gshard gate) for tests and the EP dryrun."""
    cfg = llama_tiny_config(moe_num_experts=4, moe_top_k=2,
                            intermediate_size=256)
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def llama_7b_config(**kw):
    cfg = LlamaConfig()
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def _init_weight(shape, std, dtype):
    from ..nn.initializer import Normal
    return Normal(0.0, std)(tuple(shape), dtype)


def _resolve_kv_dtype(cfg, kv_dtype=None):
    """(jnp dtype, quantized?) for the paged KV pool: explicit arg
    beats FLAGS_kv_cache_dtype beats the model compute dtype."""
    from ..framework.flags import get_flag
    name = kv_dtype if kv_dtype is not None \
        else get_flag("kv_cache_dtype", "auto")
    name = str(name)
    if name in ("auto", "", "None"):
        return cfg.compute_dtype, False
    table = {"int8": (jnp.int8, True),
             "bfloat16": (jnp.bfloat16, False),
             "bf16": (jnp.bfloat16, False),
             "float16": (jnp.float16, False),
             "fp16": (jnp.float16, False),
             "float32": (jnp.float32, False),
             "fp32": (jnp.float32, False)}
    if name not in table:
        raise ValueError(f"unknown kv_cache_dtype {name!r}; one of "
                         f"auto|{'|'.join(table)}")
    return table[name]


class LlamaRMSNorm(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        self.weight = Parameter(jnp.ones([config.hidden_size],
                                         config.storage_dtype))
        self.eps = config.rms_norm_eps

    def forward(self, x):
        (x,) = to_tensor_args(x)
        return run(lambda v, w: tpu_ops.rms_norm(v, w.astype(v.dtype),
                                                 self.eps),
                   x, self.weight, name="rms_norm")


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        self.config = config
        # this layer's kind: its window (0: full attention), whether its
        # q and k take the rotary, and whether the model has kinds at all
        # (its paged cache is then a pool a kind)
        self.window = config.layer_window(layer_idx)
        self.rotary = config.layer_rotary(layer_idx)
        self.kinds = bool(config.window_layers())
        h = config.hidden_size
        hd = config.attn_head_dim
        nh = config.num_attention_heads
        nkv = config.num_key_value_heads
        std = 1.0 / math.sqrt(h)
        pd = config.param_dtype or config.dtype
        self.q_proj = Parameter(_init_weight([h, nh * hd], std, pd))
        self.k_proj = Parameter(_init_weight([h, nkv * hd], std, pd))
        self.v_proj = Parameter(_init_weight([h, nkv * hd], std, pd))
        self.o_proj = Parameter(_init_weight([nh * hd, h], std, pd))
        if config.use_qk_norm:
            self.q_norm = Parameter(jnp.ones([hd], config.storage_dtype))
            self.k_norm = Parameter(jnp.ones([hd], config.storage_dtype))

    def _norm_names(self):
        return ["q_norm", "k_norm"] if self.config.use_qk_norm else []

    def _qk_norm(self, q, k, wqn, wkn):
        """The per-head rmsnorms of q [.., nh, hd] and k [.., nkv, hd],
        before the rotary."""
        eps = self.config.rms_norm_eps
        return (tpu_ops.xla_rms_norm(q, wqn.astype(q.dtype), eps),
                tpu_ops.xla_rms_norm(k, wkn.astype(k.dtype), eps))

    def forward(self, x, cos, sin):
        cfg = self.config
        (x,) = to_tensor_args(x)
        cos_a = cos.value if isinstance(cos, Tensor) else cos
        sin_a = sin.value if isinstance(sin, Tensor) else sin

        def _fn(v, wq, wk, wv, wo, *norms):
            from jax.ad_checkpoint import checkpoint_name
            cd = v.dtype
            b, s, h = v.shape
            q = (v @ wq.astype(cd)).reshape(b, s, cfg.num_attention_heads,
                                            cfg.attn_head_dim)
            k = (v @ wk.astype(cd)).reshape(b, s, cfg.num_key_value_heads,
                                            cfg.attn_head_dim)
            val = (v @ wv.astype(cd)).reshape(b, s,
                                              cfg.num_key_value_heads,
                                              cfg.attn_head_dim)
            if norms:
                q, k = self._qk_norm(q, k, *norms)
            if self.rotary:
                q, k = tpu_ops.apply_rope(q, k, cos_a, sin_a)
            # selective-recompute anchors: saving post-rope q/k/v lets the
            # flash backward replay only the attention kernel, not the
            # projections; the attention output feeds o_proj's weight grad
            q = checkpoint_name(q, "flash_q")
            k = checkpoint_name(k, "flash_k")
            val = checkpoint_name(val, "flash_v")
            from ..framework.flags import get_flag
            out = None
            if get_flag("sep_ring_attention"):
                # sequence-parallel composition (hybrid engine): inside
                # an activation-sharding scope with a live 'sep' axis
                # the K/V blocks rotate around the ring instead of the
                # partitioner all-gathering the sequence.  Flag read at
                # trace time — off, this branch leaves the program
                # byte-identical.
                from ..parallel.sharded_trainer import current_act_scope
                scope = current_act_scope()
                if scope is not None:
                    mesh_, _, seq_axis, _ = scope
                    if seq_axis and seq_axis in mesh_.axis_names \
                            and mesh_.shape[seq_axis] > 1 \
                            and s % mesh_.shape[seq_axis] == 0:
                        from ..ops.ring_attention import ring_attention
                        out = ring_attention(q, k, val, mesh_,
                                             seq_axis=seq_axis,
                                             causal=True)
            if out is None:
                out = tpu_ops.attention(q, k, val, causal=True,
                                        scale=cfg.attention_multiplier,
                                        block_length=cfg.block_length,
                                        window=self.window)
            out = checkpoint_name(out, "attn_out")
            return out.reshape(b, s, -1) @ wo.astype(cd)
        return run(_fn, x, self.q_proj, self.k_proj, self.v_proj,
                   self.o_proj, *[getattr(self, n)
                                  for n in self._norm_names()],
                   name="attention")

    def _decode_qkv_rope(self, x, cos, sin):
        """Shared decode-path projection + rope for BOTH KV layouts —
        the dense and paged cached paths must stay numerically
        identical here (they differ only in where K/V land)."""
        cfg = self.config
        b, s, _ = x.shape
        with jax.named_scope("attn.project"):
            q = _wo_mm(self, "q_proj", x).reshape(
                b, s, cfg.num_attention_heads, cfg.attn_head_dim)
            k = _wo_mm(self, "k_proj", x).reshape(
                b, s, cfg.num_key_value_heads, cfg.attn_head_dim)
            v = _wo_mm(self, "v_proj", x).reshape(
                b, s, cfg.num_key_value_heads, cfg.attn_head_dim)
            if cfg.use_qk_norm:
                q, k = self._qk_norm(q, k, self.q_norm.value,
                                     self.k_norm.value)
            if self.rotary:
                q, k = tpu_ops.apply_rope(q, k, cos, sin)
        return q, k, v

    def _out_proj(self, out):
        """The decode paths' o_proj product of attention's [b, s, h, d]."""
        b, s = out.shape[:2]
        with jax.named_scope("attn.project"):
            return _wo_mm(self, "o_proj", out.reshape(b, s, -1))

    def forward_cached(self, x, cos, sin, k_cache, v_cache, pos):
        """Decode-path attention: project the s_new tokens in x, write
        their K/V into the ring buffer at `pos`, attend against the
        whole cache (see ops.cached_attention).  Returns (out, k_cache,
        v_cache).  Raw jax values in and out — the generation loop is
        one jitted program, not a taped eager path."""
        q, k, v = self._decode_qkv_rope(x, cos, sin)
        pos = jnp.asarray(pos, jnp.int32)
        z = jnp.zeros((), jnp.int32)
        if pos.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (z, pos, z, z))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (z, pos, z, z))
        else:
            # per-slot write depth (continuous batching): each batch
            # row lands at its own position in its own ring buffer
            def upd(cb, xb, p):
                return jax.lax.dynamic_update_slice(cb, xb, (p, z, z))
            k_cache = jax.vmap(upd)(k_cache, k.astype(k_cache.dtype),
                                    pos)
            v_cache = jax.vmap(upd)(v_cache, v.astype(v_cache.dtype),
                                    pos)
        out = tpu_ops.cached_attention(
            q, k_cache, v_cache, pos,
            block_length=self.config.block_length, window=self.window)
        return self._out_proj(out), k_cache, v_cache

    def forward_cached_paged(self, x, cos, sin, cache, page_table, pos,
                             layer):
        """Paged-KV decode attention (ISSUE 7): same projection + rope
        as forward_cached, but K/V land in the shared page POOL via the
        slot's page table (ops.paged_kv_update — int8 pools quantize
        here) and attention gathers by page table
        (ops.paged_attention: Pallas on TPU, take-gather twin
        elsewhere).  Returns (out, cache).

        A model with KINDS of layer (config.layer_types) hands each layer
        the table of its kind's pool and its index in THAT pool: a full
        layer "k"/"v" and the slots' page table, a sliding layer
        "k_window"/"v_window" and the slots' rings (_paged_by_kind)."""
        q, k, v = self._decode_qkv_rope(x, cos, sin)
        if self.kinds:
            return self._paged_by_kind(q, k, v, cache, page_table, pos,
                                       layer)
        if cache["k"].shape[-1] != self.config.attn_head_dim:
            return self._paged_packed(q, k, v, cache, page_table, pos,
                                      layer)
        with jax.named_scope("attn.cache_write"):
            kp, vp, ks, vs = tpu_ops.paged_kv_update(
                cache["k"], cache["v"], cache.get("k_scale"),
                cache.get("v_scale"), page_table, pos, k, v, layer)
        cache = dict(cache, k=kp, v=vp)
        if ks is not None:
            cache["k_scale"], cache["v_scale"] = ks, vs
        with jax.named_scope("attn.attend"):
            out = tpu_ops.paged_attention(
                q, kp, vp, page_table, pos, layer, ks, vs,
                block_length=self.config.block_length)
        return self._out_proj(out), cache

    def _paged_packed(self, q, k, v, cache, table, pos, layer):
        """The cache write and the attention of a layer whose pool lays
        several K/V heads side by side in a row (kv_row_spec: heads
        narrower than the paged kernel's row): K and V go in as they are
        (heads adjacent in memory ARE the packed row), each query head is
        widened to the row with zeros outside its own K/V head's lanes,
        and its output read back from those lanes, so the scores and the
        output are exact.  The softmax scale is the head's own
        (attention_multiplier or head_dim ** -0.5), not the row's."""
        cfg = self.config
        b, s, nh, d = q.shape
        pk, nkv = cache["k"].shape[-1] // d, cfg.num_key_value_heads
        slot = (jnp.arange(nh) // (nh // nkv)) % pk
        place = jax.nn.one_hot(slot, pk, dtype=q.dtype)        # [nh, pk]
        with jax.named_scope("attn.cache_write"):
            kp, vp, _, _ = tpu_ops.paged_kv_update(
                cache["k"], cache["v"], None, None, table, pos,
                k.reshape(b, s, nkv // pk, pk * d),
                v.reshape(b, s, nkv // pk, pk * d), layer)
        with jax.named_scope("attn.attend"):
            qw = (q[:, :, :, None, :] * place[:, :, None]).reshape(
                b, s, nh, pk * d)
            scale = cfg.attention_multiplier or d ** -0.5
            out = tpu_ops.paged_attention(
                qw, kp, vp, table, pos, layer, scale=scale,
                block_length=cfg.block_length)
            out = jnp.sum(out.reshape(b, s, nh, pk, d)
                          * place[:, :, None], axis=3)
        return self._out_proj(out), dict(cache, k=kp, v=vp)

    def _paged_by_kind(self, q, k, v, cache, table, pos, layer):
        """The cache write and the attention of one layer of a model with
        kinds, under the scope that names the kind (`attn.window` /
        `attn.full`): both index the kind's WHOLE carried pool by
        (page, layer), as every paged model's do."""
        kn, vn = ("k_window", "v_window") if self.window else ("k", "v")
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            with jax.named_scope("attn.cache_write"):
                kp, vp, _, _ = tpu_ops.paged_kv_update(
                    cache[kn], cache[vn], None, None, table, pos, k, v,
                    layer, ring=bool(self.window))
            with jax.named_scope("attn.attend"):
                out = tpu_ops.paged_attention(q, kp, vp, table, pos, layer,
                                              window=self.window)
        return self._out_proj(out), dict(cache, **{kn: kp, vn: vp})

    # split entry points for the selective-recompute block structure
    # (forward above stays the single fused path)
    def qkv_rope(self, x, cos, sin):
        cfg = self.config
        (x,) = to_tensor_args(x)
        cos_a = cos.value if isinstance(cos, Tensor) else cos
        sin_a = sin.value if isinstance(sin, Tensor) else sin

        def _fn(v, wq, wk, wv, *norms):
            cd = v.dtype
            b, s, h = v.shape
            q = (v @ wq.astype(cd)).reshape(b, s, cfg.num_attention_heads,
                                            cfg.attn_head_dim)
            k = (v @ wk.astype(cd)).reshape(b, s, cfg.num_key_value_heads,
                                            cfg.attn_head_dim)
            val = (v @ wv.astype(cd)).reshape(b, s,
                                              cfg.num_key_value_heads,
                                              cfg.attn_head_dim)
            if norms:
                q, k = self._qk_norm(q, k, *norms)
            if self.rotary:
                q, k = tpu_ops.apply_rope(q, k, cos_a, sin_a)
            return q, k, val
        return run(_fn, x, self.q_proj, self.k_proj, self.v_proj,
                   *[getattr(self, n) for n in self._norm_names()],
                   name="qkv_rope")

    def core_attention(self, q, k, v):
        q, k, v = to_tensor_args(q, k, v)
        L, W = self.config.block_length, self.window
        return run(lambda a, b_, c: tpu_ops.attention(
                       a, b_, c, causal=True,
                       scale=self.config.attention_multiplier,
                       block_length=L, window=W),
                   q, k, v, name="core_attention")

    def output_proj(self, attn):
        (attn,) = to_tensor_args(attn)

        def _fn(a, wo):
            b, s = a.shape[0], a.shape[1]
            return a.reshape(b, s, -1) @ wo.astype(a.dtype)
        return run(_fn, attn, self.o_proj, name="attn_out_proj")


class LlamaMLAttention(nn.Layer):
    """Multi-head latent attention (DeepSeek-V2).  With x the block's
    normed input, per head h of nh:

      q = x Wq -> [q_nope | q_rope]   (q_norm over the whole head first,
                                       where use_qk_norm)
      [c | k_r] = x Wkv_a;  c = rmsnorm(c; kv_norm);  k_r, q_rope rotated
      [k_nope_h | v_h] = c Wkv_b[h]
      score_h = (q_nope_h . k_nope_h + q_rope_h . k_r) * scale

    scale = (nope + rope)^-1/2 * yarn_mscale(mscale_all_dim)^2.  A
    token's cached row is [c | k_r], c after its norm and k_r after its
    rotation.  `forward` (a whole sequence) expands k and v per head;
    the paged path ABSORBS Wkv_b into the query and the output
    (qt_h = q_nope_h Wuk[h]^T, u_h = sum p c, o_h = u_h Wuv[h]): the
    same numbers, and per (query, cached row) pair nh * (2R + r) products
    where expanding a cached row would cost R * nh * (nope + v) a step.
    Rotary layout: rotate-half (a column permutation of Wq / Wkv_a away
    from the interleaved one)."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        self.config = config
        h, nh = config.hidden_size, config.num_attention_heads
        R, nope = config.kv_lora_rank, config.qk_nope_head_dim
        r, vd = config.qk_rope_head_dim, config.v_head_dim
        pd = config.param_dtype or config.dtype
        self.q_proj = Parameter(_init_weight([h, nh * (nope + r)],
                                             h ** -0.5, pd))
        self.kv_a_proj = Parameter(_init_weight([h, R + r], h ** -0.5, pd))
        self.kv_norm = Parameter(jnp.ones([R], config.storage_dtype))
        if config.use_qk_norm:
            self.q_norm = Parameter(jnp.ones([nope + r],
                                             config.storage_dtype))
        self.kv_b_proj = Parameter(_init_weight([R, nh * (nope + vd)],
                                                R ** -0.5, pd))
        self.o_proj = Parameter(_init_weight([nh * vd, h],
                                             (nh * vd) ** -0.5, pd))
        m = 1.0
        if config.rope_scaling:
            m = tpu_ops.yarn_mscale(
                config.rope_scaling["factor"],
                config.rope_scaling.get("mscale_all_dim", 0.0))
        self.scale = (nope + r) ** -0.5 * m * m

    def _project(self, x, cos, sin):
        """(q_nope [b,s,nh,nope], q_rope [b,s,nh,r] rotated, latent rows
        [b,s,R+r]: c normed | k_r rotated) of raw jax x [b,s,h]."""
        cfg = self.config
        b, s, _ = x.shape
        nh, nope, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim)
        R, eps, cd = cfg.kv_lora_rank, cfg.rms_norm_eps, x.dtype
        q = (x @ self.q_proj.value.astype(cd)).reshape(b, s, nh, nope + r)
        if cfg.use_qk_norm:
            q = tpu_ops.xla_rms_norm(q, self.q_norm.value.astype(cd), eps)
        kv = x @ self.kv_a_proj.value.astype(cd)
        c = tpu_ops.xla_rms_norm(kv[..., :R],
                                 self.kv_norm.value.astype(cd), eps)
        q_rope, k_r = tpu_ops.xla_apply_rope(
            q[..., nope:], kv[..., None, R:], cos, sin)
        return q[..., :nope], q_rope, jnp.concatenate(
            [c, k_r[:, :, 0]], axis=-1)

    def _wkv_b(self, dtype):
        cfg = self.config
        return self.kv_b_proj.value.astype(dtype).reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)

    def _expanded(self, x, cos, sin):
        cfg = self.config
        b, s, _ = x.shape
        nh, nope, R = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.kv_lora_rank)
        q_nope, q_rope, rows = self._project(x, cos, sin)
        kv = jnp.einsum("bsc,chd->bshd", rows[..., :R],
                        self._wkv_b(x.dtype))
        k_r = jnp.broadcast_to(rows[:, :, None, R:],
                               (b, s, nh, rows.shape[-1] - R))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
        # unequal q and v head sizes: the flash kernel takes one size
        out = tpu_ops.xla_attention(q, k, kv[..., nope:], causal=True,
                                    scale=self.scale)
        return out.reshape(b, s, -1) @ self.o_proj.value.astype(x.dtype)

    def forward(self, x, cos, sin):
        (x,) = to_tensor_args(x)
        cos_a = cos.value if isinstance(cos, Tensor) else cos
        sin_a = sin.value if isinstance(sin, Tensor) else sin
        names = ["q_proj", "kv_a_proj", "kv_norm", "kv_b_proj", "o_proj"] \
            + (["q_norm"] if self.config.use_qk_norm else [])
        from ..jit import _swapped_state

        def _fn(v, *ws):
            with _swapped_state(self, names, list(ws)):
                return self._expanded(v, cos_a, sin_a)
        return run(_fn, x, *[getattr(self, n) for n in names],
                   name="mla_attention")

    def forward_cached_paged(self, x, cos, sin, cache, page_table, pos,
                             layer):
        """Paged decode / chunked-prefill attention on raw jax values:
        this step's rows go into the latent pool, every lane attends in
        latent space (absorbed).  Returns (out, cache)."""
        cfg = self.config
        b, s, _ = x.shape
        nope = cfg.qk_nope_head_dim
        with jax.named_scope("mla.project"):
            q_nope, q_rope, rows = self._project(x, cos, sin)
            w = self._wkv_b(x.dtype)
            q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w[..., :nope])
        with jax.named_scope("mla.cache_write"):
            pool = tpu_ops.latent_kv_update(cache["kv"], page_table, pos,
                                            rows, layer)
        with jax.named_scope("mla.attend"):
            u = tpu_ops.latent_paged_attention(
                q_lat, q_rope, pool, page_table, pos, layer, self.scale)
        with jax.named_scope("mla.project"):
            out = jnp.einsum("bshc,chd->bshd", u, w[..., nope:])
            out = out.reshape(b, s, -1) @ self.o_proj.value.astype(x.dtype)
        return out, dict(cache, kv=pool)


def conv_window_after(win, n_valid, taps):
    """The conv window a slot carries into its next step: of win [b,
    taps - 1 + lanes, channels] (the window it came with, then this step's
    inputs), the taps - 1 inputs before lane n_valid of each slot — the
    window shifted by the slot's valid lanes, not by the step's width."""
    at = n_valid[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)
    return jnp.take_along_axis(win, at[..., None], axis=1)


class LlamaMamba2Mixer(nn.Layer):
    """Mamba-2 mixer of a state-space layer (GraniteMoeHybrid's), one group
    of B and C.  With h the layer's normed input [b, s, hidden]:

      [z | xBC | dt] = h W_in                      (no bias)
      xBC = silu(causal depthwise conv1d(xBC) + conv_bias)   d_conv taps
      [x | B | C] = xBC;  x as H heads of P
      dt = softplus(dt + dt_bias);  A = -exp(A_log)           per head
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t            [H, P, N] fp32
      y_t = h_t C_t + D x_t
      out = rmsnorm(y * silu(z); norm) W_out       (the gate before the norm)

    The recurrence is ops.ssm_scan's (the Pallas kernel on a TPU).  The
    whole-sequence forward starts from a zero state; the paged path carries
    a state a slot in the cache: "ssm" [state layers, slots, H, P, N] (in
    config.mamba_state_dtype) and "conv" [state layers, slots, d_conv - 1,
    conv channels], the last
    inputs of the conv, each advanced by the slot's VALID lanes only (a
    pad lane that advanced a state would leave it wrong for good, where a
    pad lane's K/V row is overwritten before anyone reads it)."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        self.config = config
        h, H = config.hidden_size, config.mamba_n_heads
        inner, conv = config.mamba_inner, config.mamba_conv_dim
        pd = config.param_dtype or config.dtype
        self.in_proj = Parameter(_init_weight([h, inner + conv + H],
                                              h ** -0.5, pd))
        self.conv_weight = Parameter(_init_weight(
            [config.mamba_d_conv, conv], config.mamba_d_conv ** -0.5, pd))
        self.conv_bias = Parameter(jnp.zeros([conv], config.storage_dtype))
        # the recurrence's own numbers stay float32 whatever the dtype
        self.dt_bias = Parameter(jnp.full([H], -4.0, jnp.float32))
        self.A_log = Parameter(jnp.zeros([H], jnp.float32))
        self.D = Parameter(jnp.ones([H], jnp.float32))
        self.norm = Parameter(jnp.ones([inner], config.storage_dtype))
        self.out_proj = Parameter(_init_weight([inner, h], inner ** -0.5, pd))

    _NAMES = ("in_proj", "conv_weight", "conv_bias", "dt_bias", "A_log", "D",
              "norm", "out_proj")

    def _mix(self, h, conv_prev, pool, layer, n_valid, reset, w):
        """h [b, s, hidden] -> (out [b, s, hidden], the conv window of each
        slot's last d_conv - 1 valid inputs, the state pool).  conv_prev
        [b, d_conv - 1, channels]: the inputs before lane 0 (zero where a
        slot starts fresh); w: {name: value} of the mixer's leaves."""
        cfg = self.config
        b, s, _ = h.shape
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, K, cd = cfg.mamba_inner, cfg.mamba_d_conv, h.dtype
        with jax.named_scope("ssm.project"):
            zxbcdt = h @ w["in_proj"].astype(cd)
            z = zxbcdt[..., :inner]
            xbc = zxbcdt[..., inner:inner + cfg.mamba_conv_dim]
            dt = zxbcdt[..., inner + cfg.mamba_conv_dim:]
        with jax.named_scope("ssm.conv"):
            win = jnp.concatenate([conv_prev.astype(cd), xbc], axis=1)
            taps = w["conv_weight"].astype(jnp.float32)
            acc = sum(win[:, k:k + s].astype(jnp.float32) * taps[k]
                      for k in range(K)) + w["conv_bias"].astype(jnp.float32)
            xbc = jax.nn.silu(acc).astype(cd)
            conv_next = conv_window_after(win, n_valid, K)
        x = xbc[..., :inner].reshape(b, s, H, P)
        bm = xbc[..., inner:inner + N]
        cm = xbc[..., inner + N:]
        with jax.named_scope("ssm.scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"])
            a = -jnp.exp(w["A_log"].astype(jnp.float32))
            y, pool = tpu_ops.ssm_scan(pool, x, bm, cm, dt, a,
                                       w["D"].astype(jnp.float32), n_valid,
                                       reset, layer)
        with jax.named_scope("ssm.norm"):
            g = y.reshape(b, s, inner) \
                * jax.nn.silu(z.astype(jnp.float32))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                                  + cfg.rms_norm_eps)
            g = (g * w["norm"].astype(jnp.float32)).astype(cd)
        with jax.named_scope("ssm.out"):
            out = g @ w["out_proj"].astype(cd)
        return out, conv_next, pool

    def forward(self, x):
        """The whole sequence from a zero state (a one-layer pool)."""
        (x,) = to_tensor_args(x)
        cfg = self.config

        def _fn(v, *ws):
            b, s, _ = v.shape
            conv = jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                             jnp.float32)
            pool = jnp.zeros((1, b, cfg.mamba_n_heads, cfg.mamba_d_head,
                              cfg.mamba_d_state), jnp.float32)
            out, _, _ = self._mix(v, conv, pool, 0,
                                  jnp.full((b,), s, jnp.int32),
                                  jnp.ones((b,), jnp.int32),
                                  dict(zip(self._NAMES, ws)))
            return out
        return run(_fn, x, *[getattr(self, n) for n in self._NAMES],
                   name="mamba2_mixer")

    def forward_cached_paged(self, h, cache, pos, layer, lanes_valid=None):
        """One step's lanes of every slot through the slots' states (layer
        `layer` of the cache's "ssm" and "conv"): a slot at depth 0 starts
        from zero state, and `lanes_valid` [b] (None: every lane) lanes of
        each slot advance it.  Returns (out, cache)."""
        b, s, _ = h.shape
        n = jnp.full((b,), s, jnp.int32) if lanes_valid is None \
            else jnp.asarray(lanes_valid, jnp.int32)
        fresh = jnp.asarray(pos, jnp.int32) == 0
        conv_prev = jnp.where(fresh[:, None, None], 0,
                              cache["conv"][layer])
        out, conv_next, pool = self._mix(
            h, conv_prev, cache["ssm"], layer, n, fresh.astype(jnp.int32),
            {name: getattr(self, name).value for name in self._NAMES})
        conv = cache["conv"].at[layer].set(
            conv_next.astype(cache["conv"].dtype))
        return out, dict(cache, ssm=pool, conv=conv)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        h, i = config.hidden_size, config.intermediate_size
        std = 1.0 / math.sqrt(h)
        pd = config.param_dtype or config.dtype
        self.gate_proj = Parameter(_init_weight([h, i], std, pd))
        self.up_proj = Parameter(_init_weight([h, i], std, pd))
        self.down_proj = Parameter(_init_weight([i, h],
                                                1.0 / math.sqrt(i), pd))

    def forward(self, x):
        (x,) = to_tensor_args(x)

        def _fn(v, wg, wu, wd):
            cd = v.dtype
            return tpu_ops.swiglu(v @ wg.astype(cd),
                                  v @ wu.astype(cd)) @ wd.astype(cd)
        return run(_fn, x, self.gate_proj, self.up_proj, self.down_proj,
                   name="mlp_swiglu")


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__(dtype=config.dtype)
        self.config = config
        self._recompute = config.recompute and (
            config.recompute_layers is None
            or layer_idx < config.recompute_layers)
        # the block's two halves are chosen from the config, per layer:
        # a state-space layer mixes with its Mamba-2 mixer, any other
        # attends
        self.state_layer = layer_idx in config.state_layers()
        if self.state_layer:
            self.mamba = LlamaMamba2Mixer(config)
        else:
            self.self_attn = LlamaMLAttention(config) \
                if config.latent_attention \
                else LlamaAttention(config, layer_idx)
        self.expert_layer = config.expert_layer(layer_idx)
        if self.expert_layer:
            from ..incubate.distributed.models.moe import MoELayer
            share = {}
            if config.moe_gate == "naive":
                share = dict(dtype=config.param_dtype or config.dtype,
                             expert_bias=config.moe_expert_bias)
            if config.moe_gate == "sigmoid":
                share = dict(
                    dtype=config.param_dtype or config.dtype,
                    experts_held=(config.moe_first_expert,
                                  config.moe_num_experts),
                    router_width=config.moe_router_width
                    or config.moe_num_experts,
                    routed_scaling=config.moe_routed_scaling,
                    router_bias=config.moe_router_bias,
                    shared_hidden=config.moe_shared_experts
                    * (config.moe_intermediate_size
                       or config.intermediate_size))
            self.mlp = MoELayer(
                d_model=config.hidden_size,
                d_hidden=config.moe_intermediate_size
                or config.intermediate_size,
                num_experts=config.moe_num_experts,
                gate=config.moe_gate, top_k=config.moe_top_k,
                activation="swiglu", **share)
        else:
            self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def forward(self, x, cos, sin):
        if self._recompute:
            # per-layer activation checkpointing (reference:
            # fleet.recompute wrapping each decoder block).  "full" keeps
            # only the residual-stream boundary; "selective" splits the
            # block so the flash-attention call sits OUTSIDE the remat
            # regions — its custom_vjp residuals (q/k/v/out/lse) are
            # saved normally and the backward replays only the qkv
            # projections' norms and the MLP matmuls
            if self.config.recompute_granularity == "selective":
                return self._forward_selective(x, cos, sin)
            from ..distributed.fleet.recompute import recompute
            return recompute(self._block, x, cos, sin)
        return self._block(x, cos, sin)

    def _forward_selective(self, x, cos, sin):
        from ..distributed.fleet.recompute import recompute
        # region A: norm1 + qkv + rope.  The region outputs (post-rope
        # q/k/v) are remat boundaries — saved; internals replayed.
        with jax.named_scope("attn"):
            q, k, v = recompute(self._qkv_part, x, cos, sin)
            # flash attention runs unrematerialized (saves out + lse)
            attn = self.self_attn.core_attention(q, k, v)
        # region B: o_proj + residuals + norm2 + MLP; only the tagged
        # mid-residual is saved, the MLP matmuls replay in the backward
        policy = jax.checkpoint_policies.save_only_these_names(
            "resid_mid")
        with jax.named_scope("mlp"):
            return recompute(self._post_attention, x, attn,
                             policy=policy)

    def _qkv_part(self, x, cos, sin):
        return self.self_attn.qkv_rope(self.input_layernorm(x), cos, sin)

    def _add_norm_mid(self, x, delta):
        """Fused mid-block residual-add + RMSNorm (ops.fused_add_rms_norm
        — one Pallas VMEM pass on TPU, the identical unfused ops
        elsewhere): returns (tagged residual, normed) so the attention
        output lands in the residual stream and feeds the MLP norm
        without a second HBM round-trip (PROFILE_r05 norm slice)."""
        from jax.ad_checkpoint import checkpoint_name
        from ..parallel.sharded_trainer import constrain_activation
        norm = self.post_attention_layernorm
        (x, delta) = to_tensor_args(x, delta)

        def _fn(xv, dv, w):
            resid, normed = tpu_ops.fused_add_rms_norm(
                xv, dv, w.astype(xv.dtype), norm.eps)
            resid = checkpoint_name(constrain_activation(resid),
                                    "resid_mid")
            return resid, normed
        return run(_fn, x, delta, norm.weight, name="fused_add_rms_norm")

    def _post_attention(self, x, attn):
        """Selective-remat region B body.  Deliberately UNFUSED: the
        save_only_these_names('resid_mid') policy replays everything
        downstream of the tag, so the norm must CONSUME the tagged
        residual — the backward then rebuilds only norm+MLP from the
        saved tag.  Routing through the fused add+norm kernel here
        would put the MLP's input upstream of the tag and make the
        replay re-run output_proj per layer (an extra [T,H]x[H,H]
        matmul in every backward).  The fused kernel serves _block
        (full-/no-remat), where no such replay split exists."""
        from jax.ad_checkpoint import checkpoint_name
        from ..parallel.sharded_trainer import constrain_activation
        x = x + self.self_attn.output_proj(attn)
        x = run(lambda v: checkpoint_name(constrain_activation(v),
                                          "resid_mid"), x,
                name="tag_resid")
        x = x + self.mlp(self.post_attention_layernorm(x))
        return run(constrain_activation, x, name="constrain_resid")

    def _residual(self, y):
        """A sublayer's output as it enters the residual stream: scaled by
        the config's residual_multiplier where it has one."""
        m = self.config.residual_multiplier
        if m is None:
            return y
        if isinstance(y, Tensor):
            return run(lambda v: v * m, y, name="residual_multiplier")
        return y * m

    def _block(self, x, cos, sin):
        from ..parallel.sharded_trainer import constrain_activation
        if self.state_layer:
            a = self.mamba(self.input_layernorm(x))
        else:
            with jax.named_scope("attn"):
                a = self.self_attn(self.input_layernorm(x), cos, sin)
        x, h = self._add_norm_mid(x, self._residual(a))
        with jax.named_scope("mlp"):
            x = x + self._residual(self.mlp(h))
        return run(constrain_activation, x, name="constrain_resid")

    def _block_cached(self, x, cos, sin, attend, counters=None):
        """Shared decode-block skeleton for both KV layouts: norm →
        attend(h) → residual → norm → MLP → residual.  `attend(h)`
        returns (attn_out, new_kv_state) — the ONLY point where the
        dense ring buffer and the paged pool differ.  `counters`: the
        step's moe StepCounters (its `valid` lanes are the only ones a
        dropless expert layer routes)."""
        cfg = self.config
        ln1 = self.input_layernorm.weight.value
        ln2 = self.post_attention_layernorm.weight.value
        h = tpu_ops.rms_norm(x, ln1.astype(x.dtype), cfg.rms_norm_eps)
        attn, kv_state = attend(h)
        x = x + self._residual(attn)
        h = tpu_ops.rms_norm(x, ln2.astype(x.dtype), cfg.rms_norm_eps)
        if self.expert_layer and counters is not None:
            x = x + self._residual(self.mlp(h, valid=counters.valid,
                                            counters=counters).value)
        elif self.expert_layer:
            # MoE decode: route through the expert layer (dispatch
            # handles raw jax values; aux loss is irrelevant at decode)
            x = x + self._residual(self.mlp(h).value)
        else:
            with jax.named_scope("mlp"):
                x = x + self._residual(_wo_mm(
                    self.mlp, "down_proj",
                    tpu_ops.swiglu(_wo_mm(self.mlp, "gate_proj", h),
                                   _wo_mm(self.mlp, "up_proj", h))))
        return x, kv_state

    def forward_cached(self, x, cos, sin, k_cache, v_cache, pos):
        """Raw-jax decode block (see LlamaAttention.forward_cached)."""
        def attend(h):
            attn, kc, vc = self.self_attn.forward_cached(
                h, cos, sin, k_cache, v_cache, pos)
            return attn, (kc, vc)
        x, (k_cache, v_cache) = self._block_cached(x, cos, sin, attend)
        return x, k_cache, v_cache

    def forward_cached_paged(self, x, cos, sin, cache, page_table, pos,
                             layer, counters=None, lanes_valid=None):
        """Raw-jax paged decode block (see
        LlamaAttention.forward_cached_paged; a state-space layer's,
        LlamaMamba2Mixer.forward_cached_paged: `lanes_valid` is its)."""
        def attend(h):
            if self.state_layer:
                return self.mamba.forward_cached_paged(h, cache, pos, layer,
                                                       lanes_valid)
            return self.self_attn.forward_cached_paged(
                h, cos, sin, cache, page_table, pos, layer)
        return self._block_cached(x, cos, sin, attend, counters)


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        self.config = config
        std = 1.0 / math.sqrt(config.hidden_size)
        self.embed_tokens = Parameter(_init_weight(
            [config.vocab_size, config.hidden_size], std,
            config.param_dtype or config.dtype))
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config)

    def forward(self, input_ids):
        cfg = self.config
        (input_ids,) = to_tensor_args(input_ids)
        seq_len = input_ids.shape[1]
        cos, sin = self._rope_tables(seq_len)
        from ..parallel.sharded_trainer import constrain_activation
        # named_scope threads model-structure names into the HLO op
        # metadata and device traces (ISSUE 12): the cost ledger's
        # scope census and chrome-trace lanes attribute work per layer
        # instead of one opaque program
        with jax.named_scope("llama.embed"):
            x = run(lambda w: constrain_activation(self._embed_scale(
                        jnp.take(w, input_ids.value.astype(jnp.int32),
                                 axis=0).astype(cfg.compute_dtype))),
                    self.embed_tokens, name="embedding")
        for i, layer in enumerate(self.layers):
            with jax.named_scope(f"llama.layer{i}"):
                x = layer(x, cos, sin)
        with jax.named_scope("llama.norm"):
            return self.norm(x)

    def _embed_scale(self, x):
        m = self.config.embedding_multiplier
        return x if m is None else x * m

    def _rope_tables(self, seq_len, position_ids=None):
        """cos/sin over the rotated dims: the whole head, or an MLA
        head's rope part under the config's rope_scaling."""
        cfg = self.config
        if cfg.latent_attention:
            return tpu_ops.rope_cos_sin(
                seq_len, cfg.qk_rope_head_dim, cfg.rope_theta, jnp.float32,
                position_ids=position_ids, scaling=cfg.rope_scaling)
        return tpu_ops.rope_cos_sin(seq_len, cfg.attn_head_dim, cfg.rope_theta,
                                    jnp.float32, position_ids=position_ids)

    def kv_row_spec(self, kv_dtype=None):
        """What ONE token holds in ONE layer of the paged pool, for
        whoever sizes or names the pool without reading head counts:
        {"pools": {name: row shape}, "dtype", "scales": per-page scale
        entries a pool carries (0 unless int8), "pages_walked": the
        bound of the attention's walk over a slot's table, f(pos, q_len,
        page_size, pages_per_slot)}.  A page of a pool is
        [layers, *row[:-1], page_size, row[-1]].

        A model with KINDS of layer (config.layer_types) adds "kinds":
        {"full": {"layers", "window": 0, "pools": ("k", "v")}, "window":
        {"layers", "window": W, "pools": ("k_window", "v_window"),
        "rows": f(q_len)}}: which layers are of each kind, the pools that
        hold them (a pool's layer axis counts the kind's layers only) and
        how many rows a slot needs in a window layer, W + q_len - 1 (in a
        full layer: its whole depth); `pages_walked` then takes the
        layer's window as a fifth argument."""
        cfg = self.config
        dt, quant = _resolve_kv_dtype(cfg, kv_dtype)
        if cfg.latent_attention:
            if quant:
                raise ValueError(
                    "int8 KV is not implemented for latent (MLA) rows: a "
                    "row is [normed latent | rotated key] and one scale a "
                    "page would quantize the two against each other; use "
                    "kv_dtype auto|bfloat16|float32")
            row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim,)
            # the walk's bound follows the choice of program
            # (ops.latent_paged_attention: the kernel or the XLA walk)
            return {"pools": {"kv": row}, "dtype": dt, "scales": 0,
                    "pages_walked": tpu_ops.latent_walk_bound(
                        row[0], cfg.kv_lora_rank, dt)}
        from ..ops.pallas.paged_attention import heads_per_row, pages_walked
        # heads narrower than the paged kernel's row lie side by side in
        # one (forward_cached_paged follows the pool's row); an int8 pool
        # keeps a scale a (page, head) and window pools a head a row
        pack = 1 if quant or cfg.window_layers() else heads_per_row(
            cfg.attn_head_dim, cfg.num_key_value_heads)
        row = (cfg.num_key_value_heads // pack, cfg.attn_head_dim * pack)
        spec = {"pools": {"k": row, "v": row}, "dtype": dt,
                "scales": cfg.num_key_value_heads if quant else 0,
                "pages_walked": pages_walked}
        state = cfg.state_layers()
        if state:
            if quant:
                raise ValueError(
                    "int8 KV is not implemented for a model with state-space "
                    "layers: its attention layers are few and a slot's state "
                    "dwarfs its rows; use kv_dtype auto|bfloat16|float32")
            # a STATE a slot in each mamba layer, beside the attention
            # layers' pages: {name: (shape of one slot's, dtype)}
            spec["kinds"] = {
                "full": {"layers": tuple(
                    i for i in range(cfg.num_hidden_layers)
                    if i not in state), "window": 0, "pools": ("k", "v")},
                "state": {"layers": state, "arrays": {
                    "ssm": ((cfg.mamba_n_heads, cfg.mamba_d_head,
                             cfg.mamba_d_state),
                            jnp.dtype(cfg.mamba_state_dtype)),
                    "conv": ((cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                             cfg.compute_dtype)}}}
            return spec
        sliding = cfg.window_layers()
        if sliding:
            if quant:
                raise ValueError(
                    "int8 KV is not implemented for a model with "
                    "sliding-window layers: a ring page is rewritten in "
                    "place and would be requantised as it turns; use "
                    "kv_dtype auto|bfloat16|float32")
            W = cfg.sliding_window
            spec["pools"].update(k_window=row, v_window=row)
            spec["kinds"] = {
                "full": {"layers": tuple(
                    i for i in range(cfg.num_hidden_layers)
                    if i not in sliding), "window": 0,
                    "pools": ("k", "v")},
                "window": {"layers": sliding, "window": W,
                           "pools": ("k_window", "v_window"),
                           "rows": lambda q_len: W + q_len - 1}}
        return spec

    def init_cache(self, batch: int, max_len: int):
        """Per-layer KV ring buffers [b, max_len, n_kv, hd] in the
        compute dtype (static shapes — XLA requirement)."""
        cfg = self.config
        if cfg.latent_attention:
            raise NotImplementedError(
                "latent (MLA) attention serves through the paged pool "
                "only (init_paged_cache / forward_cached_paged); the dense "
                "ring-buffer layout has no latent form")
        if cfg.state_layers():
            raise NotImplementedError(
                "a model with state-space layers serves through the paged "
                "cache only (init_paged_cache: a state a slot beside the "
                "attention layers' pages); the dense ring buffers carry no "
                "state")
        # (a sliding-window layer's dense buffer holds the whole depth:
        # the window is its mask's, not its size's; generate() only)
        shape = (batch, max_len, cfg.num_key_value_heads, cfg.attn_head_dim)
        dt = cfg.compute_dtype
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in self.layers]

    def init_paged_cache(self, num_pages: int, page_size: int,
                         kv_dtype=None, window_pages=None, state_slots=None):
        """Paged KV pool (ISSUE 7): ONE device-resident page pool per
        K and V, [num_pages, layers, n_kv, page_size, head_dim] — one
        (page, layer, kv head) is a contiguous [page_size, head_dim]
        tile, the block the paged-attention kernel DMAs — shared by
        every serving slot through per-slot page tables.  Page 0 is
        the reserved null page (unmapped table entries point there;
        reads of its rows are position-masked).  kv_dtype: None reads
        FLAGS_kv_cache_dtype ('auto' = compute dtype; 'int8' adds
        per-page per-head fp32 scales alongside the pool).

        A model with KINDS of layer keeps a pool a kind: "k"/"v" over the
        full layers only, [num_pages, full layers, ...], behind the
        slots' page tables, and "k_window"/"v_window" over the sliding
        layers, [window_pages, sliding layers, ...]: `window_pages` =
        slots x ops.ring_pages(window, q_len, page_size), slot b's ring
        the pages b * ring .. (b + 1) * ring - 1, its own for its whole
        life (no null page: a free slot's junk lands in its own ring).

        A model with STATE-SPACE layers keeps "k"/"v" over its attention
        layers only and, beside them, each kv_row_spec "state" array for
        `state_slots` slots: "ssm" [state layers, slots, H, P, N] fp32 and
        "conv" [state layers, slots, d_conv - 1, channels], slot b's its
        own for its whole life."""
        cfg = self.config
        dt, quant = _resolve_kv_dtype(cfg, kv_dtype)
        if cfg.latent_attention:
            # ONE pool of latent rows [pages, layers, page_size, R + r]
            # (kv_row_spec refuses int8 in so many words)
            (width,) = self.kv_row_spec(kv_dtype)["pools"]["kv"]
            return {"kv": jnp.zeros((num_pages, len(self.layers),
                                     page_size, width), dt)}
        spec = self.kv_row_spec(kv_dtype)
        kinds = spec.get("kinds")
        if kinds and "state" in kinds:
            if not state_slots:
                raise ValueError("a model with state-space layers needs "
                                 "state_slots (the batcher's slots)")
            n = len(kinds["state"]["layers"])
            full = (num_pages, len(kinds["full"]["layers"])) \
                + spec["pools"]["k"][:-1] + (page_size,) \
                + spec["pools"]["k"][-1:]
            cache = {"k": jnp.zeros(full, dt), "v": jnp.zeros(full, dt)}
            for name, (shape, sdt) in kinds["state"]["arrays"].items():
                cache[name] = jnp.zeros((n, int(state_slots)) + shape, sdt)
            return cache
        if kinds:
            if not window_pages:
                raise ValueError(
                    "a model with sliding-window layers needs window_pages "
                    "(slots x ops.ring_pages(window, q_len, page_size))")
            tail = (cfg.num_key_value_heads, page_size, cfg.attn_head_dim)
            full = (num_pages, len(kinds["full"]["layers"])) + tail
            ring = (int(window_pages), len(kinds["window"]["layers"])) + tail
            return {"k": jnp.zeros(full, dt), "v": jnp.zeros(full, dt),
                    "k_window": jnp.zeros(ring, dt),
                    "v_window": jnp.zeros(ring, dt)}
        shape = (num_pages, len(self.layers), cfg.num_key_value_heads,
                 page_size, cfg.attn_head_dim)
        cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if quant:
            sshape = shape[:3]
            # scale 1.0 on untouched pages: dequant of the zero pool
            # stays zero, mirroring the dense zero-init cache
            cache["k_scale"] = jnp.ones(sshape, jnp.float32)
            cache["v_scale"] = jnp.ones(sshape, jnp.float32)
        return cache

    def forward_cached_paged(self, input_ids, cache, page_table, pos,
                             counters=None, lanes_valid=None):
        """Paged twin of forward_cached: input_ids [b, s_new]; cache:
        init_paged_cache pytree; page_table [b, pages_per_slot] int32;
        pos [b] int32 per-slot depths; counters: a moe StepCounters
        for a model with dropless expert layers (step_counter_names);
        lanes_valid [b]: how many of a slot's lanes carry a token, which a
        state-space layer's state advances by (None: all of them).
        Returns (hidden, new_cache)."""
        cfg = self.config
        s = input_ids.shape[1]
        positions = jnp.asarray(pos, jnp.int32)[..., None] \
            + jnp.arange(s, dtype=jnp.int32)
        cos, sin = self._rope_tables(s, positions)
        with jax.named_scope("llama.embed"):
            x = self._embed_scale(jnp.take(
                self.embed_tokens.value, input_ids.astype(jnp.int32),
                axis=0).astype(cfg.compute_dtype))
        state = cfg.state_layers()
        more = {"lanes_valid": lanes_valid} if state else {}
        sliding = cfg.window_layers()
        if sliding:
            # a pool a kind: each layer gets its kind's table (the slots'
            # rings are a constant of the shapes: slot b owns pages
            # b * ring .. of the window pool) and its index in THAT pool
            B = input_ids.shape[0]
            ring = cache["k_window"].shape[0] // B
            rings = jnp.arange(B, dtype=jnp.int32)[:, None] * ring \
                + jnp.arange(ring, dtype=jnp.int32)[None]
            at = {"full": 0, "window": 0}
        if state:
            # a layer's index in its kind's pool or state arrays
            at = {"full": 0, "state": 0}
        for li, layer in enumerate(self.layers):
            table, index = page_table, li
            if sliding:
                kind = "window" if li in sliding else "full"
                table = rings if kind == "window" else page_table
                index, at[kind] = at[kind], at[kind] + 1
            if state:
                kind = "state" if li in state else "full"
                index, at[kind] = at[kind], at[kind] + 1
            with jax.named_scope(f"llama.layer{li}"):
                x, cache = layer.forward_cached_paged(
                    x, cos, sin, cache, table, pos, index, counters,
                    **more)
        w = self.norm.weight.value
        with jax.named_scope("llama.norm"):
            return tpu_ops.rms_norm(x, w.astype(x.dtype),
                                    cfg.rms_norm_eps), cache

    def forward_cached(self, input_ids, cache, pos):
        """input_ids: [b, s_new] jax array; cache: init_cache pytree;
        pos: int32 scalar (uniform depth) or [b] vector (per-slot
        depths — continuous batching).  Returns (hidden [b, s_new, h],
        new_cache)."""
        cfg = self.config
        s = input_ids.shape[1]
        positions = jnp.asarray(pos, jnp.int32)[..., None] \
            + jnp.arange(s, dtype=jnp.int32)
        cos, sin = tpu_ops.rope_cos_sin(s, cfg.attn_head_dim, cfg.rope_theta,
                                        jnp.float32,
                                        position_ids=positions)
        x = jnp.take(self.embed_tokens.value,
                     input_ids.astype(jnp.int32),
                     axis=0).astype(cfg.compute_dtype)
        new_cache = []
        # zip bounds the walk at the cache's depth — an EarlyExitDraft
        # passes an n-entry cache to run only the first n blocks
        for li, (layer, (kc, vc)) in enumerate(zip(self.layers, cache)):
            with jax.named_scope(f"llama.layer{li}"):
                x, kc, vc = layer.forward_cached(x, cos, sin, kc, vc,
                                                 pos)
            new_cache.append((kc, vc))
        w = self.norm.weight.value
        with jax.named_scope("llama.norm"):
            return tpu_ops.rms_norm(x, w.astype(x.dtype),
                                    cfg.rms_norm_eps), new_cache


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.tensor import Parameter
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = Parameter(_init_weight(
                [config.hidden_size, config.vocab_size],
                1.0 / math.sqrt(config.hidden_size),
                config.param_dtype or config.dtype))

    def forward(self, input_ids):
        x = self.llama(input_ids)
        from ..framework.flags import get_flag
        if get_flag("fused_ce") and self.training:
            # fused-loss mode: compute_loss folds the lm-head matmul
            # into the chunked cross entropy — the [B, S, V] fp32
            # logits (the step's largest live buffer) never materialize
            return x
        with jax.named_scope("llama.lm_head"):
            if self.config.tie_word_embeddings:
                w = self.llama.embed_tokens
                return run(lambda v, e: self._logits_scale(
                    v @ e.T.astype(v.dtype)), x, w, name="lm_head")
            return run(lambda v, w: self._logits_scale(
                v @ w.astype(v.dtype)), x, self.lm_head, name="lm_head")

    def init_cache(self, batch: int, max_len: int):
        return self.llama.init_cache(batch, max_len)

    def init_paged_cache(self, num_pages: int, page_size: int,
                         kv_dtype=None, window_pages=None, state_slots=None):
        return self.llama.init_paged_cache(num_pages, page_size,
                                           kv_dtype, window_pages,
                                           state_slots)

    def kv_row_spec(self, kv_dtype=None):
        return self.llama.kv_row_spec(kv_dtype)

    def block_diffusion(self):
        """How the model generates, for the batcher (beside kv_row_spec
        and step_counter_names): None = autoregressive, else
        LlamaConfig.block_diffusion()'s description."""
        return self.config.block_diffusion()

    def step_counter_names(self):
        """Names of the int32 counts a serve step of this model
        accumulates on the device (incubate...moe.StepCounters): those
        of its dropless expert layers, () without any."""
        cfg = self.config
        if cfg.moe_num_experts > 0 and cfg.moe_gate in ("naive", "sigmoid"):
            from ..incubate.distributed.models.moe import COUNTER_NAMES
            return COUNTER_NAMES
        return ()

    def _lm_logits(self, x):
        """Decode-path lm head: tied embeddings stay unquantized (the
        embedding is gathered elsewhere); an untied head rides the
        weight-only packed path like every other decode matmul."""
        with jax.named_scope("llama.lm_head"):
            if self.config.tie_word_embeddings:
                w = self.llama.embed_tokens.value
                return self._logits_scale(x @ w.T.astype(x.dtype))
            return self._logits_scale(_wo_mm(self, "lm_head", x))

    def _logits_scale(self, logits):
        d = self.config.logits_scaling
        return logits if d is None else logits / d

    def forward_cached_paged(self, input_ids, cache, page_table, pos,
                             counters=None, head_lanes=None,
                             lanes_valid=None):
        """Paged twin of forward_cached: returns (logits, new_cache).
        `head_lanes` n: logits [b, n, V] of the first n lanes only (the
        block of a model that generates by diffusion: no other lane of a
        step needs the head).  `lanes_valid`: LlamaModel's."""
        x, cache = self.llama.forward_cached_paged(input_ids, cache,
                                                   page_table, pos,
                                                   counters, lanes_valid)
        if head_lanes is not None:
            x = x[:, :head_lanes]
        return self._lm_logits(x), cache

    def forward_cached(self, input_ids, cache, pos):
        """Raw-jax cached step for the generation loop: returns
        (logits [b, s_new, V], new_cache)."""
        x, cache = self.llama.forward_cached(input_ids, cache, pos)
        return self._lm_logits(x), cache

    def early_exit_draft(self, num_layers: int) -> "EarlyExitDraft":
        """Self-drafting draft model (ISSUE 11 speculative decoding):
        a decode-capable view over this model's FIRST `num_layers`
        decoder blocks + the final norm and lm head — no extra weights
        resident, and because the draft reads the target's own
        Parameter objects it sees the serving scan's swapped-in values
        with zero extra plumbing."""
        return EarlyExitDraft(self, num_layers)

    def generate(self, input_ids, max_new_tokens=32, **kw):
        """KV-cached generation (see inference.generation.generate)."""
        from ..inference.generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)

    def compute_loss(self, logits, labels):
        """Next-token cross entropy in fp32 (reference:
        ParallelCrossEntropy over vocab-sharded logits), via the shared
        nn.functional.fused_cross_entropy.  Under FLAGS_fused_ce the
        forward hands HIDDEN states here and the lm-head matmul folds
        into the chunked fused loss (no [B, S, V] fp32 logits)."""
        (out,) = to_tensor_args(logits)
        (labels,) = to_tensor_args(labels)
        cfg = self.config
        # fused-mode detection mirrors forward()'s gate (flag + training)
        # rather than inferring from shapes — a shape heuristic silently
        # mis-dispatches when hidden_size == vocab_size.  The shape check
        # only guards against logits computed OUTSIDE fused mode.
        from ..framework.flags import get_flag
        if get_flag("fused_ce") and self.training \
                and out.shape[-1] == cfg.hidden_size:
            if cfg.tie_word_embeddings:
                w, tw = self.llama.embed_tokens, True
            else:
                w, tw = self.lm_head, False
            loss = F.fused_cross_entropy(out, labels, weight=w,
                                         transpose_weight=tw, shift=True,
                                         name="causal_lm_loss_fused")
        else:
            loss = F.fused_cross_entropy(out, labels, shift=True,
                                         name="causal_lm_loss")
        if self.config.moe_num_experts > 0 \
                and self.config.moe_aux_weight:
            # load-balance auxiliary loss from each MoE block's last
            # forward (reference: moe_layer keeps l_aux the same way)
            for layer in self.llama.layers:
                aux = getattr(layer.mlp, "l_aux", None)
                if aux is not None:
                    # l_aux is the Tensor run() produced — re-wrapping
                    # would sever the recorded vjp chain (eager path)
                    if not isinstance(aux, Tensor):
                        aux = Tensor(aux)
                    loss = loss + self.config.moe_aux_weight * aux
        return loss


class EarlyExitDraft:
    """Early-exit draft over a LlamaForCausalLM (speculative decoding's
    self-drafting mode): embed → layers[:n] → final norm → lm head,
    with its OWN dense KV cache (n layers deep).  A plain adapter, not
    a Layer — it owns no parameters (state_dict would double-count the
    target's), so the serving scan passes it no values; the target's
    `_swapped_state` covers every weight the draft reads."""

    def __init__(self, model: "LlamaForCausalLM", num_layers: int):
        n_total = model.config.num_hidden_layers
        n = int(num_layers)
        if not 0 < n <= n_total:
            raise ValueError(f"early-exit draft needs 1..{n_total} "
                             f"layers (got {n})")
        self._model = model
        self.num_layers = n
        self.config = model.config

    def init_cache(self, batch: int, max_len: int):
        cfg = self.config
        shape = (batch, max_len, cfg.num_key_value_heads, cfg.attn_head_dim)
        dt = cfg.compute_dtype
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(self.num_layers)]

    def forward_cached(self, input_ids, cache, pos):
        # LlamaModel.forward_cached zips layers with the cache, so the
        # n-entry draft cache bounds the walk to the first n blocks —
        # the target's own decode path (positions, rope, final norm)
        # IS the draft path, with nothing duplicated to drift
        m = self._model
        x, new_cache = m.llama.forward_cached(input_ids, cache, pos)
        return m._lm_logits(x), new_cache


def shard_llama_tp(model: LlamaForCausalLM, mesh):
    """Annotate llama params with TP NamedShardings over the 'mp' axis
    (megatron layout: column for q/k/v/gate/up, row for o/down; vocab for
    embed/lm_head).  Reference: mp_layers.py usage in llama pretraining."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(p, spec):
        p._value = jax.device_put(p.value, NamedSharding(mesh, spec))

    put(model.llama.embed_tokens, P("mp", None))
    if not model.config.tie_word_embeddings:
        put(model.lm_head, P(None, "mp"))
    for layer in model.llama.layers:
        put(layer.self_attn.q_proj, P(None, "mp"))
        put(layer.self_attn.k_proj, P(None, "mp"))
        put(layer.self_attn.v_proj, P(None, "mp"))
        put(layer.self_attn.o_proj, P("mp", None))
        put(layer.mlp.gate_proj, P(None, "mp"))
        put(layer.mlp.up_proj, P(None, "mp"))
        put(layer.mlp.down_proj, P("mp", None))
    return model
