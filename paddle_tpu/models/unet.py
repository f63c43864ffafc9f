"""Diffusion UNet — baseline config 5 (Stable-Diffusion-style UNet,
samples/sec; BASELINE.json).

Reference capability: the reference trains SD/ERNIE-ViL-class multimodal
models through its Fleet engine (paddle's diffusers port builds on
`paddle.nn` conv/attention blocks).

TPU-native design: a UNet2DConditionModel-shaped network — timestep
sinusoidal embedding + MLP, down/up resnet blocks with GroupNorm+SiLU,
self+cross attention at the lower resolutions through
paddle_tpu.ops.attention (Pallas flash kernel where shapes allow), skip
connections, trained with the standard epsilon-prediction MSE.  NCHW
layout (XLA picks TPU-native conv layouts itself)."""
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

__all__ = ["UNetConfig", "UNet2DConditionModel", "unet_tiny_config",
           "unet_sd_config"]


@dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_channels: tuple = (320, 640, 1280)
    layers_per_block: int = 2
    attention_levels: tuple = (1, 2)   # indices into block_channels
    num_attention_heads: int = 8
    cross_attention_dim: int = 768
    norm_groups: int = 32
    dtype: str = "float32"


def unet_tiny_config(**kw):
    cfg = UNetConfig(in_channels=4, out_channels=4,
                     block_channels=(32, 64), layers_per_block=1,
                     attention_levels=(1,), num_attention_heads=4,
                     cross_attention_dim=32, norm_groups=8)
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def unet_sd_config(**kw):
    cfg = UNetConfig()
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def timestep_embedding(t, dim, max_period=10000.0):
    """Sinusoidal timestep embedding (DDPM recipe)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


class ResnetBlock(nn.Layer):
    def __init__(self, in_c, out_c, temb_c, groups):
        super().__init__()
        self.norm1 = nn.GroupNorm(groups, in_c)
        self.conv1 = nn.Conv2D(in_c, out_c, 3, padding=1)
        self.temb_proj = nn.Linear(temb_c, out_c)
        self.norm2 = nn.GroupNorm(groups, out_c)
        self.conv2 = nn.Conv2D(out_c, out_c, 3, padding=1)
        self.skip = nn.Conv2D(in_c, out_c, 1) if in_c != out_c else None

    def forward(self, x, temb):
        h = self.conv1(nn.functional.silu(self.norm1(x)))
        t = self.temb_proj(nn.functional.silu(temb))
        (h, t) = to_tensor_args(h, t)
        h = run(lambda a, b: a + b[:, :, None, None], h, t,
                name="temb_add")
        h = self.conv2(nn.functional.silu(self.norm2(h)))
        return h + (self.skip(x) if self.skip is not None else x)


class AttentionBlock(nn.Layer):
    """Self-attention + cross-attention over flattened spatial tokens
    (the transformer block of SD's UNet, single depth)."""

    def __init__(self, channels, heads, cross_dim, groups):
        super().__init__()
        self.heads = heads
        self.norm = nn.GroupNorm(groups, channels)
        self.to_q = nn.Linear(channels, channels, bias_attr=False)
        self.to_k = nn.Linear(channels, channels, bias_attr=False)
        self.to_v = nn.Linear(channels, channels, bias_attr=False)
        self.to_out = nn.Linear(channels, channels)
        self.norm_cross = nn.LayerNorm(channels)
        self.cross_q = nn.Linear(channels, channels, bias_attr=False)
        self.cross_k = nn.Linear(cross_dim, channels, bias_attr=False)
        self.cross_v = nn.Linear(cross_dim, channels, bias_attr=False)
        self.cross_out = nn.Linear(channels, channels)
        self.norm_ff = nn.LayerNorm(channels)
        self.ff1 = nn.Linear(channels, channels * 4)
        self.ff2 = nn.Linear(channels * 4, channels)

    def _attend(self, q, k, v):
        (q, k, v) = to_tensor_args(q, k, v)
        heads = self.heads

        def _fn(qv, kv, vv):
            b, sq, c = qv.shape
            sk = kv.shape[1]
            hd = c // heads
            out = tpu_ops.attention(qv.reshape(b, sq, heads, hd),
                                    kv.reshape(b, sk, heads, hd),
                                    vv.reshape(b, sk, heads, hd),
                                    causal=False)
            return out.reshape(b, sq, c)
        return run(_fn, q, k, v, name="unet_attention")

    def forward(self, x, context):
        (x,) = to_tensor_args(x)
        b, c, hgt, wid = x.shape

        def to_tokens(v):
            return run(lambda a: a.reshape(a.shape[0], a.shape[1], -1)
                       .swapaxes(1, 2), *to_tensor_args(v),
                       name="nchw_to_tokens")

        # pre-norm transformer block over spatial tokens: each branch
        # normalizes its own input; the residual stream carries the RAW
        # tokens (SD's proj-out residual shape — no double-added norm)
        h = to_tokens(x)
        normed = to_tokens(self.norm(x))
        h = h + self.to_out(self._attend(
            self.to_q(normed), self.to_k(normed), self.to_v(normed)))
        hc = self.norm_cross(h)
        h = h + self.cross_out(self._attend(
            self.cross_q(hc), self.cross_k(context),
            self.cross_v(context)))
        h = h + self.ff2(nn.functional.gelu(self.ff1(self.norm_ff(h))))
        return run(lambda v: v.swapaxes(1, 2).reshape(b, c, hgt, wid),
                   *to_tensor_args(h), name="tokens_to_nchw")


class UNet2DConditionModel(nn.Layer):
    def __init__(self, config: UNetConfig):
        super().__init__(dtype=config.dtype)
        cfg = self.config = config
        chans = cfg.block_channels
        temb_c = chans[0] * 4
        g = cfg.norm_groups
        self.temb1 = nn.Linear(chans[0], temb_c)
        self.temb2 = nn.Linear(temb_c, temb_c)
        self.conv_in = nn.Conv2D(cfg.in_channels, chans[0], 3, padding=1)

        self.down_blocks = nn.LayerList()
        self.down_attns = nn.LayerList()
        self.downsamplers = nn.LayerList()
        in_c = chans[0]
        for level, out_c in enumerate(chans):
            for _ in range(cfg.layers_per_block):
                self.down_blocks.append(ResnetBlock(in_c, out_c, temb_c,
                                                    g))
                self.down_attns.append(
                    AttentionBlock(out_c, cfg.num_attention_heads,
                                   cfg.cross_attention_dim, g)
                    if level in cfg.attention_levels else None)
                in_c = out_c
            self.downsamplers.append(
                nn.Conv2D(out_c, out_c, 3, stride=2, padding=1)
                if level < len(chans) - 1 else None)

        self.mid_block1 = ResnetBlock(in_c, in_c, temb_c, g)
        self.mid_attn = AttentionBlock(in_c, cfg.num_attention_heads,
                                       cfg.cross_attention_dim, g)
        self.mid_block2 = ResnetBlock(in_c, in_c, temb_c, g)

        self.up_blocks = nn.LayerList()
        self.up_attns = nn.LayerList()
        self.upsamplers = nn.LayerList()
        skip_chans = self._skip_channels()
        for level in reversed(range(len(chans))):
            out_c = chans[level]
            for _ in range(cfg.layers_per_block + 1):
                skip_c = skip_chans.pop()
                self.up_blocks.append(ResnetBlock(in_c + skip_c, out_c,
                                                  temb_c, g))
                self.up_attns.append(
                    AttentionBlock(out_c, cfg.num_attention_heads,
                                   cfg.cross_attention_dim, g)
                    if level in cfg.attention_levels else None)
                in_c = out_c
            self.upsamplers.append(
                nn.Conv2D(out_c, out_c, 3, padding=1)
                if level > 0 else None)

        self.norm_out = nn.GroupNorm(g, chans[0])
        self.conv_out = nn.Conv2D(chans[0], cfg.out_channels, 3,
                                  padding=1)
        if cfg.dtype != "float32":
            # flax idiom: fp32 params as masters, convs/linears/norm
            # outputs in the compute dtype (nn.set_compute_dtype)
            nn.set_compute_dtype(self, cfg.dtype)

    def _skip_channels(self):
        cfg = self.config
        chans = cfg.block_channels
        skips = [chans[0]]
        for level, out_c in enumerate(chans):
            skips.extend([out_c] * cfg.layers_per_block)
            if level < len(chans) - 1:
                skips.append(out_c)
        return skips

    def forward(self, sample, timesteps, encoder_hidden_states):
        cfg = self.config
        (sample,) = to_tensor_args(sample)
        t = timesteps.value if isinstance(timesteps, Tensor) \
            else jnp.asarray(timesteps)
        temb = Tensor(timestep_embedding(t, cfg.block_channels[0]))
        temb = self.temb2(nn.functional.silu(self.temb1(temb)))

        h = self.conv_in(sample)
        skips = [h]
        i = 0
        for level in range(len(cfg.block_channels)):
            for _ in range(cfg.layers_per_block):
                h = self.down_blocks[i](h, temb)
                if self.down_attns[i] is not None:
                    h = self.down_attns[i](h, encoder_hidden_states)
                skips.append(h)
                i += 1
            ds = self.downsamplers[level]
            if ds is not None:
                h = ds(h)
                skips.append(h)

        h = self.mid_block1(h, temb)
        h = self.mid_attn(h, encoder_hidden_states)
        h = self.mid_block2(h, temb)

        i = 0
        for li, level in enumerate(reversed(
                range(len(cfg.block_channels)))):
            for _ in range(cfg.layers_per_block + 1):
                skip = skips.pop()
                (h2, s2) = to_tensor_args(h, skip)
                h = run(lambda a, b: jnp.concatenate([a, b], axis=1),
                        h2, s2, name="unet_skip_concat")
                h = self.up_blocks[i](h, temb)
                if self.up_attns[i] is not None:
                    h = self.up_attns[i](h, encoder_hidden_states)
                i += 1
            us = self.upsamplers[li]
            if us is not None:
                (h2,) = to_tensor_args(h)
                h = run(lambda v: jax.image.resize(
                    v, (v.shape[0], v.shape[1], v.shape[2] * 2,
                        v.shape[3] * 2), "nearest"), h2,
                    name="unet_upsample")
                h = us(h)

        return self.conv_out(nn.functional.silu(self.norm_out(h)))

    def compute_loss(self, pred_eps, true_eps):
        (pred_eps, true_eps) = to_tensor_args(pred_eps, true_eps)
        return run(lambda p, e: jnp.mean(
            (p.astype(jnp.float32) - e.astype(jnp.float32)) ** 2),
            pred_eps, true_eps, name="eps_mse")
