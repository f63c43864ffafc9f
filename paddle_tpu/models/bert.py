"""BERT family — baseline config 2 (BERT-base pretraining, DP +
sharding stage-1; BASELINE.json).

Reference capability: PaddleNLP-style BERT built on the reference's nn
stack (`python/paddle/nn/` MultiHeadAttention/TransformerEncoder) and
trained through Fleet DP with sharding stage 1.

TPU-native design: the encoder is plain paddle_tpu.nn layers (Linear /
LayerNorm / Embedding / Dropout) — everything jits into one XLA program
via jit.TrainStep / ShardedTrainStep; attention dispatches through
paddle_tpu.ops.attention (Pallas flash kernel on TPU, non-causal path).
Post-LN residual blocks and learned position embeddings match the
original BERT; the MLM decoder ties the word-embedding matrix.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..framework.dispatch import run, to_tensor_args
from .. import ops as tpu_ops

__all__ = ["BertConfig", "BertModel", "BertForMaskedLM",
           "bert_base_config", "bert_tiny_config"]


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.0
    layer_norm_eps: float = 1e-12
    # original BERT's gelu IS the tanh approximation
    # (google-research/bert modeling.py gelu); the erf form costs ~25ms
    # per step on v5e (fp32 VPU erf) for identical quality
    hidden_act: str = "gelu_tanh"
    # COMPUTE dtype (flax idiom): params are always fp32 masters; when
    # dtype is low-precision, nn.set_compute_dtype switches the matmul/
    # embedding/LN-output path to it (see nn.set_compute_dtype)
    dtype: str = "float32"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def compute_dtype(self):
        from ..framework import dtypes
        return dtypes.to_jax(self.dtype)


def bert_base_config(**kw):
    cfg = BertConfig()
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def bert_tiny_config(**kw):
    cfg = BertConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, intermediate_size=128,
                     max_position_embeddings=64, type_vocab_size=2)
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size,
                                            config.hidden_size)
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size,
                                                  config.hidden_size)
        self.layer_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_eps)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        (input_ids,) = to_tensor_args(input_ids)
        seq = input_ids.shape[1]
        pos = Tensor(jnp.arange(seq, dtype=jnp.int32)[None, :])
        x = self.word_embeddings(input_ids) \
            + self.position_embeddings(pos)
        if token_type_ids is not None:
            x = x + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertSelfAttention(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.query = nn.Linear(h, h)
        self.key = nn.Linear(h, h)
        self.value = nn.Linear(h, h)
        self.out = nn.Linear(h, h)

    def forward(self, x, attention_mask=None):
        cfg = self.config
        q, k, v = self.query(x), self.key(x), self.value(x)
        (q, k, v) = to_tensor_args(q, k, v)
        mask = attention_mask.value if isinstance(attention_mask, Tensor) \
            else attention_mask
        if mask is not None and mask.ndim == 2:
            # reference surface: [batch, seq] keep-mask (1=attend,
            # 0=pad) → broadcastable bool [b, 1, 1, sk]
            mask = (mask > 0)[:, None, None, :]

        def _fn(qv, kv, vv):
            b, s, h = qv.shape
            nh, hd = cfg.num_attention_heads, cfg.head_dim
            out = tpu_ops.attention(
                qv.reshape(b, s, nh, hd), kv.reshape(b, s, nh, hd),
                vv.reshape(b, s, nh, hd), mask=mask, causal=False)
            return out.reshape(b, s, h)
        ctx = run(_fn, q, k, v, name="bert_attention")
        return self.out(ctx)


class BertLayer(nn.Layer):
    """Post-LN transformer block (original BERT residual order)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.attention = BertSelfAttention(config)
        self.attn_norm = nn.LayerNorm(config.hidden_size,
                                      epsilon=config.layer_norm_eps)
        self.intermediate = nn.Linear(config.hidden_size,
                                      config.intermediate_size)
        self.output = nn.Linear(config.intermediate_size,
                                config.hidden_size)
        self.out_norm = nn.LayerNorm(config.hidden_size,
                                     epsilon=config.layer_norm_eps)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self._act = getattr(config, "hidden_act", "gelu_tanh")

    def forward(self, x, attention_mask=None):
        with jax.named_scope("attn"):
            x = self.attn_norm(x + self.dropout(
                self.attention(x, attention_mask)))
        with jax.named_scope("mlp"):
            y = self.output(nn.functional.gelu(
                self.intermediate(x),
                approximate=self._act == "gelu_tanh"))
            return self.out_norm(x + self.dropout(y))


class BertModel(nn.Layer):
    """Reference surface: paddlenlp BertModel(input_ids, token_type_ids,
    attention_mask) -> (sequence_output, pooled_output)."""

    embeddings_cls: type = None   # subclass hook (ERNIE task-type table)

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        emb_cls = type(self).embeddings_cls or BertEmbeddings
        self.embeddings = emb_cls(config)
        self.layers = nn.LayerList(
            [BertLayer(config) for _ in range(config.num_hidden_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)
        if config.dtype != "float32":
            nn.set_compute_dtype(self, config.dtype)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        # named_scope: model-structure names in HLO metadata + device
        # traces (ISSUE 12 per-layer attribution; see llama)
        with jax.named_scope("bert.embed"):
            x = self.embeddings(input_ids, token_type_ids)
        for i, layer in enumerate(self.layers):
            with jax.named_scope(f"bert.layer{i}"):
                x = layer(x, attention_mask)
        with jax.named_scope("bert.pooler"):
            pooled = nn.functional.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForMaskedLM(nn.Layer):
    """MLM head: dense + gelu + LN + tied-embedding decoder."""

    backbone_cls: type = None     # subclass hook (ERNIE backbone)

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.bert = (type(self).backbone_cls or BertModel)(config)
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.transform_norm = nn.LayerNorm(config.hidden_size,
                                           epsilon=config.layer_norm_eps)
        from ..framework.tensor import Parameter
        self.decoder_bias = Parameter(
            jnp.zeros([config.vocab_size], jnp.float32))
        if config.dtype != "float32":
            nn.set_compute_dtype(self, config.dtype)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq_out, _ = self.bert(input_ids, token_type_ids, attention_mask)
        x = self.transform_norm(nn.functional.gelu(
            self.transform(seq_out),
            approximate=self.config.hidden_act == "gelu_tanh"))
        from ..framework.flags import get_flag
        if get_flag("fused_ce") and self.training:
            # fused-loss mode: compute_loss folds the tied-embedding
            # decoder matmul into the chunked cross entropy — the
            # [tokens, vocab] logits (2 GB of HBM traffic at bench
            # shapes) never materialize
            return x
        w = self.bert.embeddings.word_embeddings.weight
        return run(lambda v, e, b: v @ e.T.astype(v.dtype)
                   + b.astype(v.dtype),
                   *to_tensor_args(x, w, self.decoder_bias),
                   name="mlm_decoder")

    def compute_loss(self, logits, labels, ignore_index=-100):
        """Masked-position cross entropy, fp32 accumulation, via the
        shared nn.functional.fused_cross_entropy (CE = lse − picked;
        under FLAGS_fused_ce the decoder matmul folds into the chunked
        fused loss and only [chunk, vocab] logits slices ever exist)."""
        (out, labels) = to_tensor_args(logits, labels)
        cfg = self.config
        # mirrors forward()'s fused gate (flag + training) — see
        # models/llama.py: shape inference alone mis-dispatches when
        # hidden_size == vocab_size
        from ..framework.flags import get_flag
        if get_flag("fused_ce") and self.training \
                and out.shape[-1] == cfg.hidden_size:
            return nn.functional.fused_cross_entropy(
                out, labels,
                weight=self.bert.embeddings.word_embeddings.weight,
                bias=self.decoder_bias, transpose_weight=True,
                ignore_index=ignore_index, name="mlm_loss_fused")
        return nn.functional.fused_cross_entropy(
            out, labels, ignore_index=ignore_index, name="mlm_loss")
