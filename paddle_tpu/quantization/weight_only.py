"""Weight-only quantization for the decode path (ISSUE 11 tentpole).

Reference: `python/paddle/nn/quant/quantized_linear.py`
(weight_quantize / weight_only_linear: int8 per-channel and int4
group-wise packed weights with fp16/bf16 scales, dequant fused into
the serving matmul) — the `quantization` layer SURVEY.md names as
in-scope Paddle capability surface.

TPU-native: decode is HBM-bandwidth-bound — every weight byte
crosses HBM once per generated token,
so storing the linear weights at 1 byte (int8) or half a byte (int4)
per element is a direct tokens/s multiplier.  `quantize_model` packs a
llama/gpt model's linear weights IN PLACE: each target Parameter's
value becomes the packed int8 array and a sibling `<name>_scale`
Parameter carries the scales, so both ride the model's state_dict
straight into the compiled serve scan (the batcher swaps params by
name — no new plumbing).  The decode forwards
(models/llama.py/models/gpt.py `_wo_mm`) then dispatch those matmuls
to ops.quant_matmul — a Pallas kernel that dequantizes in VMEM fused
into the matmul on TPU, a bit-exact jnp twin elsewhere.

Quantization math (symmetric absmax, matching quanters._fake_quant's
grid so observer-calibrated scales port 1:1):

  int8   per-output-channel: scale[n] = amax(|w[:, n]|) / 127
  int4   group-wise along K: scale[g, n] = amax(|w[g*G:(g+1)*G, n]|)/7,
         values packed two nibbles per byte in the half-split layout
         (ops.pack_int4); groups never straddle the pack halves

Scales are stored in the weight's own dtype (bf16 weights keep bf16
scales — the reference's fp16/bf16 scale convention); dequant widens
to fp32 before the multiply in both the kernel and the twin.

A quantized model is SERVING-ONLY: the packed weights replace the fp
originals (that is the point — no second resident copy), so training
forwards and optimizers must not touch it.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import ops as tpu_ops
from ..framework.flags import get_flag
from ..framework.tensor import Parameter

__all__ = ["quantize_weight", "dequantize_weight", "quantize_model",
           "weight_pool_bytes", "packed_bytes", "WEIGHT_ONLY_DTYPES"]

WEIGHT_ONLY_DTYPES = ("int8", "int4")

# the decode-path matmul weights per model family: (owner attr path is
# resolved structurally — any layer holding ALL the listed params is a
# quantization site).  Embeddings are excluded: they are gathered, not
# matmul'd, and gpt's tied lm head reads the embedding.
_LLAMA_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
_LLAMA_MLP = ("gate_proj", "up_proj", "down_proj")
_GPT_BLOCK = ("qkv", "proj", "fc_in", "fc_out")


def _resolve(dtype=None, group_size=None):
    dtype = str(dtype if dtype is not None
                else get_flag("weight_only_dtype", "none"))
    if dtype in ("none", "", "None"):
        return None, None
    if dtype not in WEIGHT_ONLY_DTYPES:
        raise ValueError(f"unknown weight_only_dtype {dtype!r}; one of "
                         f"none|{'|'.join(WEIGHT_ONLY_DTYPES)}")
    group_size = int(group_size if group_size is not None
                     else get_flag("weight_only_group_size", 64))
    return dtype, group_size


def quantize_weight(w, dtype="int8", group_size=64):
    """(packed, scales) for a [K, N] weight.  int8: packed [K, N] int8,
    scales [N]; int4: packed [K//2, N] int8 (ops.pack_int4 half-split),
    scales [K//group_size, N].  Scales keep w's dtype."""
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"weight-only quantization expects a 2-D "
                         f"weight (got shape {tuple(w.shape)})")
    K, N = w.shape
    wf = w.astype(jnp.float32)
    if dtype == "int8":
        amax = jnp.max(jnp.abs(wf), axis=0)                     # [N]
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(wf / scale[None]), -127, 127) \
            .astype(jnp.int8)
        return q, scale.astype(w.dtype)
    if dtype != "int4":
        raise ValueError(f"unknown weight-only dtype {dtype!r}")
    g = int(group_size)
    if K % 2 or (K // 2) % g:
        raise ValueError(
            f"int4 group_size {g} must divide K/2 (K={K}); pick a "
            f"group size that divides half the input dimension")
    wg = wf.reshape(K // g, g, N)
    amax = jnp.max(jnp.abs(wg), axis=1)                   # [K//g, N]
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wg / scale[:, None, :]), -7, 7) \
        .astype(jnp.int32).reshape(K, N)
    return tpu_ops.pack_int4(q), scale.astype(w.dtype)


def dequantize_weight(packed, scales, dtype="int8", group_size=64):
    """fp32 [K, N] reconstruction (ops.dequant_weight — the canonical
    math both the kernel and the twin share)."""
    return tpu_ops.dequant_weight(packed, scales, dtype, group_size)


def _quantize_param(layer, name, dtype, group_size):
    p = getattr(layer, name)
    packed, scale = quantize_weight(p.value, dtype, group_size)
    # replace the fp Parameter's VALUE in place (its identity — tied
    # references, sharding annotations on other params — survives) and
    # register the sibling scale so both ride state_dict()
    p._value = packed
    setattr(layer, name + "_scale", Parameter(scale))


def _mark(layer, dtype, group_size):
    # plain attributes (not params/sublayers): __setattr__ routes them
    # to the instance dict
    layer._wo_dtype = dtype
    layer._wo_group = group_size


def quantize_model(model, dtype=None, group_size=None):
    """Pack `model`'s decode-path linear weights in place (llama
    attention/MLP projections + untied lm head, gpt block matmuls).
    Resolves dtype/group_size from FLAGS_weight_only_dtype /
    FLAGS_weight_only_group_size when not given.  Idempotent: a model
    already quantized at the same config is returned untouched; a
    DIFFERENT config raises (the packed weights cannot be re-packed).
    Returns the model; `model._weight_only` records the config."""
    dtype, group_size = _resolve(dtype, group_size)
    if dtype is None:
        return model
    prev = getattr(model, "_weight_only", None)
    if prev is not None:
        if prev != {"dtype": dtype, "group_size": group_size}:
            raise ValueError(
                f"model already weight-only quantized at {prev}; "
                f"cannot re-quantize to {dtype}/g{group_size}")
        return model
    sites = 0
    for _, sub in model.named_sublayers(include_self=True):
        params = sub._parameters
        for group in (_LLAMA_ATTN, _LLAMA_MLP, _GPT_BLOCK):
            if all(n in params for n in group):
                for n in group:
                    _quantize_param(sub, n, dtype, group_size)
                _mark(sub, dtype, group_size)
                sites += len(group)
                break
    # llama's untied lm head lives on the CausalLM wrapper itself
    if "lm_head" in getattr(model, "_parameters", {}):
        _quantize_param(model, "lm_head", dtype, group_size)
        _mark(model, dtype, group_size)
        sites += 1
    if not sites:
        raise ValueError(
            "quantize_model found no weight-only quantization sites "
            "(expected llama q/k/v/o + gate/up/down or gpt "
            "qkv/proj/fc_in/fc_out parameters)")
    object.__setattr__(model, "_weight_only",
                       {"dtype": dtype, "group_size": group_size})
    return model


def _target_params(model):
    """The Parameters quantize_model targets (packed or not), plus any
    installed scale siblings — the decode weight pool."""
    out = []
    for _, sub in model.named_sublayers(include_self=True):
        params = sub._parameters
        names = []
        for group in (_LLAMA_ATTN, _LLAMA_MLP, _GPT_BLOCK):
            if all(n in params for n in group):
                names += list(group)
                break
        if sub is model and "lm_head" in params:
            names.append("lm_head")
        for n in names:
            out.append(params[n])
            if n + "_scale" in params:
                out.append(params[n + "_scale"])
    return out


def weight_pool_bytes(model) -> int:
    """Resident bytes of the decode weight pool (the quantized targets
    + scales) as the model currently stands — the bench's weight-HBM
    metric, comparable across none/int8/int4."""
    return int(sum(int(np.prod(p.value.shape)) * p.value.dtype.itemsize
                   for p in _target_params(model)))


def packed_bytes(model, dtype, group_size=None) -> int:
    """What weight_pool_bytes WOULD be after quantize_model(model,
    dtype) — pure shape arithmetic, no packing (the bench's int8-vs-
    int4 sizing comparison must not mutate or copy the model).  The
    model must be unquantized."""
    if getattr(model, "_weight_only", None) is not None:
        raise ValueError("packed_bytes expects an unquantized model")
    dtype, group_size = _resolve(dtype, group_size)
    total = 0
    for p in _target_params(model):
        K, N = p.value.shape
        sdt = p.value.dtype.itemsize
        if dtype is None:
            total += K * N * sdt
        elif dtype == "int8":
            total += K * N + N * sdt
        else:
            total += (K // 2) * N + (K // group_size) * N * sdt
    return int(total)
