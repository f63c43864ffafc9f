"""Shared optimizer-update plumbing for the jitted train steps.

Reference: multi-precision (master weight) AdamW — `optimizer/adamw.py`
`_multi_precision`/`_master_weights` and the fused CUDA kernels
(`phi/kernels/gpu/adamw_kernel.cu` MultiPrecision variants).  TPU-native:
the fp32 master lives INSIDE the optimizer state pytree, so it is donated,
sharded by the trainer's ZeRO policy alongside the moments (ZeRO-1/2
"master shards"), and checkpointed with the rest of the state.  With
fp32 params (flax param_dtype idiom) the param itself is the master and
no separate copy exists.

`apply_update` is used by both jit.TrainStep and parallel.ShardedTrainStep:

  - state contains "master": the pure update rule runs on the fp32
    master and the half-precision param is re-derived by a cast
  - fp32 param + {moment1, moment2} state: the param is updated in place
  - on TPU with Adam/AdamW hyper-params, dispatches to the Pallas
    fused_adamw kernel (single pass, in-place state)
  - under a multi-device mesh, the fused kernel is shard_map-wrapped
    over the caller-provided PartitionSpec so every chip updates only
    its own ZeRO shard (a bare pallas_call has no SPMD rule — GSPMD
    would replicate the state on every chip)
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework.flags import get_flag, define_flag

__all__ = ["apply_update", "apply_updates", "maybe_master_state",
           "wants_master"]

# The kernel is chosen for what it does IN the step program, where XLA
# schedules its own update fusion worse than it does standalone (round
# 5, pre-ledger; the two have not been compared since).  What the ledger
# shows is the kernel in the train cell: `fused_adamw_roofline` 96.8 %
# with 78 ms a step of `[rows, 1024]` relayouts around it (PR 29), and
# since PR 31, which blocks each leaf in its own shape, the kernel alone:
# 15.8 % of the step's device time at 87 % of its roofline (PERF.md
# section 5).
define_flag("use_fused_adamw", True,
            "dispatch jitted Adam/AdamW updates to the fused Pallas kernel "
            "on TPU (measured faster in-step; off = XLA's own fusion)")
define_flag("fused_adamw_interpret", False,
            "allow the fused AdamW path off-TPU (Pallas interpret mode) — "
            "for tests exercising the shard_map-wrapped kernel on CPU")
define_flag("multi_tensor_adamw", False,
            "flatten same-(wd, dtype, state-layout) SMALL params into one "
            "fused AdamW call inside the jitted step (reference: "
            "fused_adam_kernel.cu multi-tensor); large params keep "
            "per-param calls.  Default OFF by measurement: neutral on "
            "llama-1B (17,582 vs 17,559 tok/s) but -4.3% on bert-base "
            "(137,151 vs 143,389) — the concat/split traffic outweighs "
            "saved launches when small params are a large fraction")

# params below this element count are batched into one flat update; the
# big matmul weights above it dominate HBM traffic, not launch count
_MULTI_TENSOR_MAX = 1 << 20

_HALF = (jnp.bfloat16, jnp.float16)


def wants_master(optimizer, param_value) -> bool:
    return (getattr(optimizer, "_multi_precision", False)
            and jnp.dtype(param_value.dtype).type in
            tuple(jnp.dtype(t).type for t in _HALF))


def maybe_master_state(optimizer, param, state: dict) -> dict:
    """Add the fp32 master copy to a freshly-initialised state dict."""
    if wants_master(optimizer, param.value):
        state = dict(state)
        state["master"] = param.value.astype(jnp.float32)
    return state


def _is_adam_hp(hp):
    return {"b1", "b2", "eps", "decoupled"} <= set(hp)


def _fusable(hp, state, p_dtype):
    if not (_is_adam_hp(hp) and get_flag("use_fused_adamw")):
        return False
    if jax.default_backend() != "tpu" \
            and not get_flag("fused_adamw_interpret"):
        return False
    keys = set(state) - {"ef"}   # the error-feedback residual rides along
    if "master" in keys:
        return {"moment1", "moment2", "master"} == keys
    return ({"moment1", "moment2"} == keys
            and jnp.dtype(p_dtype) == jnp.float32)


def _pad_spec(spec, ndim):
    parts = tuple(spec) if spec is not None else ()
    return P(*(parts + (None,) * (ndim - len(parts))))


def apply_update(upd, p, g, s, lr, wd, step_i, hp, fused_ok=True,
                 mesh=None, spec=None):
    """One parameter's optimizer update inside a jitted step.

    upd: the optimizer class's pure `_update(param, grad, state, lr, wd,
    step, **hp)`.  Handles the master-weight indirection and the fused
    TPU kernel; falls back to the pure rule everywhere else.

    fused_ok=False with mesh/spec given: the state is sharded — the
    fused kernel is wrapped in shard_map over `spec` (the state's
    PartitionSpec on `mesh`) so each chip updates its local shard.
    Without mesh/spec, sharded callers fall back to the pure rule
    (GSPMD partitions it).
    """
    fusable = _fusable(hp, s, jnp.dtype(p.dtype))
    if fusable and (fused_ok or (mesh is not None and spec is not None)):
        from ..ops.pallas.fused_adamw import fused_adamw
        master = s.get("master", p)
        ef = s.get("ef")
        kw = dict(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], wd=wd,
                  decoupled=hp["decoupled"], out_dtype=p.dtype)
        if fused_ok:
            out = fused_adamw(g, s["moment1"], s["moment2"], master,
                              lr, step_i, ef=ef, **kw)
        else:
            sp = _pad_spec(spec, g.ndim)
            n_state = 4 if ef is None else 5

            def local(g_, m_, v_, mst_, lr_, st_, *ef_):
                return fused_adamw(g_, m_, v_, mst_, lr_, st_,
                                   ef=ef_[0] if ef_ else None, **kw)

            out = jax.shard_map(
                local, mesh=mesh,
                in_specs=(sp, sp, sp, sp, P(), P())
                + ((sp,) if ef is not None else ()),
                out_specs=(sp,) * n_state,
                check_vma=False,
            )(g, s["moment1"], s["moment2"], master,
              jnp.asarray(lr, jnp.float32), jnp.asarray(step_i, jnp.int32),
              *(() if ef is None else (ef,)))
        new_p, m, v, mst = out[:4]
        ns = {"moment1": m, "moment2": v}
        if "master" in s:
            ns["master"] = mst
        if ef is not None:
            ns["ef"] = out[4]
        return new_p, ns
    if "master" in s:
        rest = {k: v for k, v in s.items() if k != "master"}
        new_master, ns = upd(s["master"], g.astype(jnp.float32), rest,
                             lr, wd, step_i, **hp)
        ns = dict(ns)
        ns["master"] = new_master
        return new_master.astype(p.dtype), ns
    return upd(p, g, s, lr, wd, step_i, **hp)


def apply_updates(upd, params, grads, states, lr, wds, step_i, hp,
                  lr_scales=None):
    """All parameters' updates inside a single-device jitted step.

    Multi-tensor batching (reference: `fused_adam_kernel.cu` multi-tensor
    AdamW): the MANY small params (norm scales, biases) are raveled and
    concatenated per (wd, lr_scale, param dtype, moment dtypes, master?)
    group and updated with ONE fused kernel call, then split back — the
    per-launch overhead of ~N small kernels goes away while the copy
    traffic added by the concat/split is bounded by the group's total
    bytes (small by construction; params above _MULTI_TENSOR_MAX keep
    their per-param call because for them traffic, not launches, is the
    cost).  Falls back to the per-param path wholesale when the flag is
    off or the state layout is not the fused Adam one.
    """
    if lr_scales is None:
        lr_scales = [1.0] * len(params)

    def _one(i):
        ls = lr_scales[i]
        return apply_update(upd, params[i], grads[i], states[i],
                            lr if ls == 1.0 else lr * ls, wds[i],
                            step_i, hp)

    if not get_flag("multi_tensor_adamw"):
        out = [_one(i) for i in range(len(params))]
        return [o[0] for o in out], [o[1] for o in out]

    groups: dict = {}
    for i, (p, s) in enumerate(zip(params, states)):
        # ef states stay per-param: grouping is measured perf-neutral at
        # best, and the residual would need its own concat/split lane
        if (p.size < _MULTI_TENSOR_MAX and "ef" not in s
                and _fusable(hp, s, jnp.dtype(p.dtype))):
            key = (float(wds[i]), float(lr_scales[i]),
                   jnp.dtype(p.dtype).name, "master" in s,
                   jnp.dtype(s["moment1"].dtype).name,
                   jnp.dtype(s["moment2"].dtype).name)
            groups.setdefault(key, []).append(i)

    new_params = [None] * len(params)
    new_states = [None] * len(params)
    grouped = set()
    from ..ops.pallas.fused_adamw import fused_adamw
    for (wd, ls, _pd, has_master, _m1d, _m2d), idxs in groups.items():
        if len(idxs) < 2:
            continue
        grouped.update(idxs)
        sizes = [params[i].size for i in idxs]
        flat_g = jnp.concatenate([grads[i].ravel() for i in idxs])
        flat_m1 = jnp.concatenate(
            [states[i]["moment1"].ravel() for i in idxs])
        flat_m2 = jnp.concatenate(
            [states[i]["moment2"].ravel() for i in idxs])
        flat_mst = jnp.concatenate(
            [(states[i]["master"] if has_master else params[i]).ravel()
             for i in idxs])
        new_p, m1, m2, mst = fused_adamw(
            flat_g, flat_m1, flat_m2, flat_mst,
            lr if ls == 1.0 else lr * ls, step_i,
            b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], wd=wd,
            decoupled=hp["decoupled"], out_dtype=params[idxs[0]].dtype)
        splits = [int(x) for x in itertools.accumulate(sizes)][:-1]
        p_parts, m1_parts, m2_parts = (jnp.split(a, splits)
                                       for a in (new_p, m1, m2))
        mst_parts = jnp.split(mst, splits) if has_master else None
        for j, i in enumerate(idxs):
            shape = params[i].shape
            new_params[i] = p_parts[j].reshape(shape)
            ns = {"moment1": m1_parts[j].reshape(shape),
                  "moment2": m2_parts[j].reshape(shape)}
            if has_master:
                ns["master"] = mst_parts[j].reshape(shape)
            new_states[i] = ns
    for i in range(len(params)):
        if i not in grouped:
            new_params[i], new_states[i] = _one(i)
    return new_params, new_states
