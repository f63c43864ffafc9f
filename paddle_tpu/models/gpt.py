"""GPT family — baseline config 4 (GPT-3-style hybrid TP+PP+sharding
pretraining; BASELINE.json).

Reference capability: PaddleNLP-style GPT trained by the Fleet hybrid
engine (the reference's flagship static hybrid config).

TPU-native design mirrors models/llama.py: parameters carry optional TP
NamedShardings ('mp' axis — GSPMD inserts the collectives), fp32
param_dtype + bf16 compute supported, attention through
paddle_tpu.ops.attention (Pallas flash kernel, causal), pre-LN blocks
with learned position embeddings and gelu MLP (the GPT-2/3 recipe, vs
llama's RMSNorm/rope/swiglu)."""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor, Parameter
from ..framework.dispatch import run, to_tensor_args
from .. import ops as tpu_ops
from .llama import _wo_mm

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny_config",
           "gpt3_6b7_config", "shard_gpt_tp"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    max_position_embeddings: int = 2048
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str | None = None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


def gpt_tiny_config(**kw):
    cfg = GPTConfig(vocab_size=256, hidden_size=64,
                    intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=128,
                    dtype="float32")
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def gpt3_6b7_config(**kw):
    cfg = GPTConfig()
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def _w(shape, std, dtype):
    from ..nn.initializer import Normal
    return Normal(0.0, std)(tuple(shape), dtype)


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__(dtype=config.dtype)
        cfg = self.config = config
        h, i = cfg.hidden_size, cfg.intermediate_size
        pd = cfg.param_dtype or cfg.dtype
        std = 0.02
        self.ln1 = nn.LayerNorm(h, epsilon=cfg.layer_norm_epsilon)
        self.qkv = Parameter(_w([h, 3 * h], std, pd))
        self.qkv_bias = Parameter(jnp.zeros([3 * h], jnp.float32))
        self.proj = Parameter(_w([h, h], std / math.sqrt(
            2 * cfg.num_hidden_layers), pd))
        self.proj_bias = Parameter(jnp.zeros([h], jnp.float32))
        self.ln2 = nn.LayerNorm(h, epsilon=cfg.layer_norm_epsilon)
        self.fc_in = Parameter(_w([h, i], std, pd))
        self.fc_in_bias = Parameter(jnp.zeros([i], jnp.float32))
        self.fc_out = Parameter(_w([i, h], std / math.sqrt(
            2 * cfg.num_hidden_layers), pd))
        self.fc_out_bias = Parameter(jnp.zeros([h], jnp.float32))

    def forward(self, x):
        cfg = self.config
        (x,) = to_tensor_args(x)

        def _attn(v, wqkv, bqkv, wo, bo):
            cd = v.dtype
            b, s, h = v.shape
            nh, hd = cfg.num_attention_heads, cfg.head_dim
            qkv = v @ wqkv.astype(cd) + bqkv.astype(cd)
            q, k, val = jnp.split(qkv, 3, axis=-1)
            out = tpu_ops.attention(
                q.reshape(b, s, nh, hd), k.reshape(b, s, nh, hd),
                val.reshape(b, s, nh, hd), causal=True)
            return out.reshape(b, s, h) @ wo.astype(cd) + bo.astype(cd)

        def _mlp(v, wi, bi, wo, bo):
            cd = v.dtype
            y = jax.nn.gelu(v @ wi.astype(cd) + bi.astype(cd),
                            approximate=True)
            return y @ wo.astype(cd) + bo.astype(cd)

        with jax.named_scope("attn"):
            a = run(_attn, self.ln1(x), self.qkv, self.qkv_bias,
                    self.proj, self.proj_bias, name="gpt_attention")
            x = x + a
        with jax.named_scope("mlp"):
            m = run(_mlp, self.ln2(x), self.fc_in, self.fc_in_bias,
                    self.fc_out, self.fc_out_bias, name="gpt_mlp")
        return x + m

    def _ln(self, ln, x):
        return tpu_ops.layer_norm(x, ln.weight.value.astype(x.dtype),
                                  ln.bias.value.astype(x.dtype),
                                  self.config.layer_norm_epsilon)

    def forward_cached(self, x, k_cache, v_cache, pos):
        """Raw-jax decode block (the llama forward_cached idiom, GPT
        recipe: pre-LN, combined qkv, gelu MLP, learned positions
        applied at the embedding).  The matmuls ride `_wo_mm`, so a
        weight-only quantized gpt decodes through ops.quant_matmul."""
        cfg = self.config
        cd = x.dtype
        b, s, h = x.shape
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        hn = self._ln(self.ln1, x)
        qkv = _wo_mm(self, "qkv", hn) + self.qkv_bias.value.astype(cd)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nh, hd)
        v = v.reshape(b, s, nh, hd)
        pos = jnp.asarray(pos, jnp.int32)
        z = jnp.zeros((), jnp.int32)
        if pos.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (z, pos, z, z))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (z, pos, z, z))
        else:
            def upd(cb, xb, p):
                return jax.lax.dynamic_update_slice(cb, xb, (p, z, z))
            k_cache = jax.vmap(upd)(k_cache, k.astype(k_cache.dtype),
                                    pos)
            v_cache = jax.vmap(upd)(v_cache, v.astype(v_cache.dtype),
                                    pos)
        out = tpu_ops.cached_attention(q, k_cache, v_cache, pos)
        a = _wo_mm(self, "proj", out.reshape(b, s, h)) \
            + self.proj_bias.value.astype(cd)
        x = x + a
        hn = self._ln(self.ln2, x)
        y = jax.nn.gelu(_wo_mm(self, "fc_in", hn)
                        + self.fc_in_bias.value.astype(cd),
                        approximate=True)
        m = _wo_mm(self, "fc_out", y) \
            + self.fc_out_bias.value.astype(cd)
        return x + m, k_cache, v_cache


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__(dtype=config.dtype)
        cfg = self.config = config
        pd = cfg.param_dtype or cfg.dtype
        self.wte = Parameter(_w([cfg.vocab_size, cfg.hidden_size], 0.02,
                                pd))
        self.wpe = Parameter(_w([cfg.max_position_embeddings,
                                 cfg.hidden_size], 0.01, pd))
        self.layers = nn.LayerList(
            [GPTBlock(cfg) for _ in range(cfg.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids):
        cfg = self.config
        (input_ids,) = to_tensor_args(input_ids)
        seq = input_ids.shape[1]
        # named_scope: model-structure names in HLO metadata + device
        # traces (ISSUE 12 per-layer attribution; see llama)
        with jax.named_scope("gpt.embed"):
            x = run(lambda w, p: (jnp.take(w, input_ids.value.astype(
                        jnp.int32), axis=0) + p[:seq]).astype(
                            cfg.compute_dtype),
                    self.wte, self.wpe, name="gpt_embedding")
        for i, layer in enumerate(self.layers):
            with jax.named_scope(f"gpt.layer{i}"):
                x = layer(x)
        with jax.named_scope("gpt.norm"):
            return self.ln_f(x)

    def init_cache(self, batch: int, max_len: int):
        """Per-layer KV ring buffers [b, max_len, n_heads, hd] (the
        llama init_cache contract — GPT is MHA, so n_kv == n_heads)."""
        cfg = self.config
        shape = (batch, max_len, cfg.num_attention_heads, cfg.head_dim)
        dt = cfg.compute_dtype
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in self.layers]

    def forward_cached(self, input_ids, cache, pos):
        """input_ids [b, s_new]; pos scalar or per-slot [b] vector
        (continuous batching).  Returns (hidden, new_cache).  Learned
        positions index wpe by each token's GLOBAL position, mirroring
        the rope position_ids of the llama decode path."""
        cfg = self.config
        s = input_ids.shape[1]
        positions = jnp.clip(
            jnp.asarray(pos, jnp.int32)[..., None]
            + jnp.arange(s, dtype=jnp.int32),
            0, cfg.max_position_embeddings - 1)
        x = (jnp.take(self.wte.value, input_ids.astype(jnp.int32),
                      axis=0)
             + jnp.take(self.wpe.value, positions, axis=0)) \
            .astype(cfg.compute_dtype)
        new_cache = []
        for li, (layer, (kc, vc)) in enumerate(zip(self.layers, cache)):
            with jax.named_scope(f"gpt.layer{li}"):
                x, kc, vc = layer.forward_cached(x, kc, vc, pos)
            new_cache.append((kc, vc))
        with jax.named_scope("gpt.norm"):
            return tpu_ops.layer_norm(
                x, self.ln_f.weight.value.astype(x.dtype),
                self.ln_f.bias.value.astype(x.dtype),
                cfg.layer_norm_epsilon), new_cache


class GPTForCausalLM(nn.Layer):
    """Tied-embedding LM head (GPT-2/3 recipe)."""

    def __init__(self, config: GPTConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.gpt = GPTModel(config)

    def forward(self, input_ids):
        x = self.gpt(input_ids)
        from ..framework.flags import get_flag
        if get_flag("fused_ce") and self.training:
            # fused-loss mode: compute_loss folds the tied-embedding
            # lm-head matmul into the chunked cross entropy
            return x
        w = self.gpt.wte
        return run(lambda v, e: v @ e.T.astype(v.dtype), x, w,
                   name="gpt_lm_head")

    def init_cache(self, batch: int, max_len: int):
        return self.gpt.init_cache(batch, max_len)

    def forward_cached(self, input_ids, cache, pos):
        """Raw-jax cached decode step: (logits [b, s_new, V],
        new_cache).  The tied lm head reads the embedding (gathered at
        embed time), so it stays unquantized under weight-only."""
        x, cache = self.gpt.forward_cached(input_ids, cache, pos)
        w = self.gpt.wte.value
        return x @ w.T.astype(x.dtype), cache

    def generate(self, input_ids, max_new_tokens=32, **kw):
        """KV-cached generation (see inference.generation.generate)."""
        from ..inference.generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)

    def compute_loss(self, logits, labels):
        """Next-token cross entropy via the shared
        nn.functional.fused_cross_entropy (hidden-state fused mode
        under FLAGS_fused_ce — see models/llama.py)."""
        (out, labels) = to_tensor_args(logits, labels)
        cfg = self.config
        # mirrors forward()'s fused gate (flag + training) — see
        # models/llama.py: shape inference alone mis-dispatches when
        # hidden_size == vocab_size
        from ..framework.flags import get_flag
        if get_flag("fused_ce") and self.training \
                and out.shape[-1] == cfg.hidden_size:
            return nn.functional.fused_cross_entropy(
                out, labels, weight=self.gpt.wte, transpose_weight=True,
                shift=True, name="gpt_lm_loss_fused")
        return nn.functional.fused_cross_entropy(
            out, labels, shift=True, name="gpt_lm_loss")


def shard_gpt_tp(model: GPTForCausalLM, mesh):
    """Megatron TP layout over the 'mp' axis: qkv/fc_in column-sharded,
    proj/fc_out row-sharded, embeddings vocab-sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(p, spec):
        p._value = jax.device_put(p.value, NamedSharding(mesh, spec))

    put(model.gpt.wte, P("mp", None))
    for layer in model.gpt.layers:
        put(layer.qkv, P(None, "mp"))
        put(layer.qkv_bias, P("mp"))
        put(layer.proj, P("mp", None))
        put(layer.fc_in, P(None, "mp"))
        put(layer.fc_in_bias, P("mp"))
        put(layer.fc_out, P("mp", None))
    return model
