"""ShardedTrainStep — hybrid-parallel compiled training.

See package docstring for the reference mapping.  The strategy is encoded
entirely in array shardings:

  stage 0: params+opt replicated, batch sharded on (dp, sharding) → XLA
           emits the grad allreduce (= reference fused_allreduce_gradients)
  stage 1: opt states sharded on 'sharding'                      (ZeRO-1)
  stage 2: stage 1 + grads materialized sharded (reduce-scatter) (ZeRO-2)
  stage 3: params themselves sharded; XLA allgathers per use     (ZeRO-3)

TP/SEP shardings already attached to params compose: specs are merged, so
e.g. a q_proj [h, mp] weight at stage 3 becomes [sharding → h, mp].
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.tensor import Tensor
from ..framework import random as prandom
from ..ops import kernel_mesh_scope

__all__ = ["ShardedTrainStep", "make_batch_sharding",
           "activation_sharding_scope", "constrain_activation",
           "current_act_scope"]


_ACT_SCOPE: list = []


def current_act_scope():
    """The ambient (mesh, batch_axes, seq_axis, seq_dim) pushed by the
    innermost `activation_sharding_scope`, or None outside one.  Lets
    ops deep inside a model (e.g. attention routing to the sep-axis
    ring kernel) discover the live sequence axis without threading the
    mesh through every call signature."""
    return _ACT_SCOPE[-1] if _ACT_SCOPE else None


class activation_sharding_scope:
    """While active (during tracing), `constrain_activation` pins
    [batch, seq, hidden] activations to the data-parallel layout: batch
    over the dp/sharding axes, hidden replicated.  Without these anchors
    GSPMD sometimes propagates a ZeRO-3 param's 'sharding' dim into the
    activations instead of allgathering the param, forcing
    replicate-then-reshard ("involuntary full rematerialization") at the
    remat boundaries."""

    def __init__(self, mesh, batch_axes, seq_axis=None, seq_dim=1):
        self._entry = (mesh, batch_axes, seq_axis, seq_dim)
        # on more than one device the Pallas kernels the model
        # dispatches run per shard (ops.kernel_mesh_scope)
        self._kernels = kernel_mesh_scope(mesh, batch_axes) \
            if mesh.size > 1 else contextlib.nullcontext()

    def __enter__(self):
        _ACT_SCOPE.append(self._entry)
        self._kernels.__enter__()
        return self

    def __exit__(self, *exc):
        self._kernels.__exit__(*exc)
        _ACT_SCOPE.pop()
        return False


def constrain_activation(v):
    """Apply the ambient activation sharding (no-op outside the scope)."""
    if not _ACT_SCOPE or v.ndim < 2:
        return v
    mesh, batch_axes, seq_axis, seq_dim = _ACT_SCOPE[-1]
    from ..distributed.topology import batch_partition_spec
    spec = batch_partition_spec(mesh, v.shape, batch_axes)
    if seq_axis and seq_axis in mesh.axis_names \
            and mesh.shape[seq_axis] > 1 and v.ndim > seq_dim \
            and v.shape[seq_dim] % mesh.shape[seq_axis] == 0:
        spec[seq_dim] = seq_axis
    return jax.lax.with_sharding_constraint(
        v, NamedSharding(mesh, P(*spec)))


def shard_batch(mesh: Mesh, arr, batch_axes=("dp", "sharding"),
                seq_axis=None, seq_dim=1):
    """Place one batch array: batch dim over the data axes, seq dim
    over `seq_axis` when present AND divisible (same guard as
    `constrain_activation` — a ragged seq stays replicated rather than
    erroring).  Shared by ShardedTrainStep and OffloadPipelineStep."""
    from ..distributed.topology import batch_partition_spec
    spec = batch_partition_spec(mesh, arr.shape, batch_axes)
    if seq_axis and seq_axis in mesh.axis_names \
            and mesh.shape[seq_axis] > 1 and arr.ndim > seq_dim \
            and arr.shape[seq_dim] % mesh.shape[seq_axis] == 0:
        spec[seq_dim] = seq_axis
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def make_batch_sharding(mesh: Mesh, ndim: int, batch_axes=("dp", "sharding")):
    axes = tuple(a for a in batch_axes if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    if not axes:
        return NamedSharding(mesh, P(*([None] * ndim)))
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))


def _current_spec(arr) -> P:
    sh = getattr(arr, "sharding", None)
    if isinstance(sh, NamedSharding):
        spec = list(sh.spec)
        spec += [None] * (arr.ndim - len(spec))
        return spec
    return [None] * arr.ndim


def _add_axis_to_spec(spec, axis_name, shape, axis_size, mesh=None):
    """Choose a dim for an extra sharding axis.

    Preference 1: stack onto an already-sharded dim (e.g. the TP dim) —
    the weight is then allgathered at use, and no new sharded dim leaks
    into activation shardings (putting the ZeRO axis on a weight's
    hidden dim makes GSPMD shard activations' hidden dim, forcing
    full-remat reshards).  Preference 2: largest free dim that divides.
    """
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    if mesh is not None:
        for i in order:
            cur = spec[i]
            if cur is None:
                continue
            axes = cur if isinstance(cur, tuple) else (cur,)
            local = shape[i]
            for a in axes:
                local //= mesh.shape[a]
            if local % axis_size == 0 and local > 1:
                spec = list(spec)
                spec[i] = tuple(axes) + (axis_name,)
                return spec
    for i in order:
        if spec[i] is None and shape[i] % axis_size == 0 and shape[i] > 1:
            spec = list(spec)
            spec[i] = axis_name
            return spec
    return spec  # leave replicated if nothing divides


class ShardedTrainStep:
    def __init__(self, model, optimizer, mesh: Mesh, loss_fn=None,
                 sharding_stage: int = 0, rematerialize: bool = False,
                 batch_axes=("dp", "sharding"), donate: bool = True,
                 seq_axis: Optional[str] = None, seq_dim: int = 1,
                 offload=False, offload_prefetch_depth: int = 1,
                 offload_cast_dtype="bfloat16", grad_scaler=None,
                 comm_overlap=None, comm_bucket_mb=None,
                 grad_comm_dtype=None):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.stage = sharding_stage
        self.remat = rematerialize
        # nonfinite-step guard (FLAGS_skip_nonfinite_steps): lazily
        # built; the optional GradScaler gets backoff() on bad steps
        self._guard = None
        self._scaler = grad_scaler
        # comm/compute overlap engine (ISSUE 16): bucketed gradient
        # collectives issued with the backward.  None -> the FLAGS
        # (read once HERE, at build time — the flags-off step program
        # is byte-identical, bench-asserted).  Ignored by the
        # offload="stream" pipeline, which owns its own scheduling.
        from ..framework.flags import get_flag as _gf
        self._comm_overlap = bool(_gf("comm_overlap")) \
            if comm_overlap is None else bool(comm_overlap)
        self._comm_bucket_mb = float(_gf("comm_bucket_mb") or 32.0) \
            if comm_bucket_mb is None else float(comm_bucket_mb)
        self._grad_comm_dtype = (_gf("grad_comm_dtype") or "auto") \
            if grad_comm_dtype is None else str(grad_comm_dtype)
        self._overlap_plan = None
        self._comm_profile = None
        # offload="stream": the explicit double-buffered per-layer
        # streaming pipeline (offload_pipeline.py) — forward/backward
        # prefetch windows + in-backward optimizer, replacing the
        # scheduler-dependent param_stream path for block-stacked
        # models.  The pipeline's host stacks are authoritative between
        # steps: call sync_to_model() before checkpointing or running
        # eval through the module API.
        self.batch_axes = batch_axes
        self.seq_axis = seq_axis
        self.seq_dim = seq_dim
        self._donate = donate
        self._pipeline = None
        if offload == "stream":
            from .offload_pipeline import OffloadPipelineStep
            self._pipeline = OffloadPipelineStep(
                model, optimizer, mesh, loss_fn=loss_fn,
                prefetch_depth=offload_prefetch_depth,
                cast_dtype=offload_cast_dtype, batch_axes=batch_axes,
                donate=donate, seq_axis=seq_axis, seq_dim=seq_dim,
                grad_scaler=grad_scaler)
            self.offload = True
            self.offload_params = True
            return
        # host offload (reference: group_sharded_stage3.py `offload` —
        # fp32 master + moments, and with offload=True also the
        # PARAMETER slices, parked on CPU).  TPU-native:
        #   offload=True      — optimizer-state pytree lives in
        #     pinned_host; each step streams it through HBM for the
        #     update and the out_shardings land it back on the host.
        #   offload="params"  — additionally the parameters themselves
        #     park on the host.  Per-block recompute regions stream
        #     their own params in-graph (parallel/param_stream.py), so
        #     the backward replay re-streams them and grads materialize
        #     host-side: HBM holds ~one block's params + activations —
        #     the lever from the ~2B ceiling to 4B+ on a 16G chip.
        # In-step streaming needs the runtime's memory-space annotate op
        # (TPU); the CPU backend lacks it, so there the host parking
        # happens at step boundaries outside jit (identical placement
        # semantics — what the CPU-mesh tests validate).
        self.offload = bool(offload)
        self.offload_params = offload in ("params", "all")
        self._stream_offload = bool(offload) and \
            jax.default_backend() == "tpu"
        self._names = [n for n, _ in model.named_parameters()]
        all_names = list(model.state_dict().keys())
        self._buf_names = [n for n in all_names if n not in self._names]
        self._compiled = None
        self._opt_states = None
        # AOT executable store (telemetry.compile_cache): only populated
        # while FLAGS_compile_cache_dir is armed
        self._aot = {}
        self._setup_shardings()

    @classmethod
    def from_strategy(cls, model, optimizer, mesh, strategy, **kw):
        """Build from a fleet DistributedStrategy: when the
        `strategy.sharding` master switch is on, sharding_configs
        supplies {stage, offload, offload_prefetch_depth,
        offload_cast_dtype} (reference: sharding_configs in
        distributed_strategy.proto only drive GroupSharded when
        strategy.sharding is enabled)."""
        sc = dict(getattr(strategy, "sharding_configs", {}) or {}) \
            if getattr(strategy, "sharding", False) else {}
        kw.setdefault("sharding_stage", sc.get("stage", 0 if not sc
                                               else 1))
        kw.setdefault("offload", sc.get("offload", False))
        kw.setdefault("offload_prefetch_depth",
                      sc.get("offload_prefetch_depth", 1))
        kw.setdefault("offload_cast_dtype",
                      sc.get("offload_cast_dtype", "bfloat16"))
        # comm-overlap knobs (ISSUE 16), Paddle names:
        # sharding_configs.comm_overlap gates the engine;
        # strategy.fuse_grad_size_in_MB sizes the buckets (the same
        # field Paddle's fused_allreduce passes read).  None keeps the
        # FLAGS defaults.
        if "comm_overlap" in sc:
            kw.setdefault("comm_overlap", bool(sc["comm_overlap"]))
        fuse_mb = getattr(strategy, "fuse_grad_size_in_MB", None)
        if fuse_mb:
            kw.setdefault("comm_bucket_mb", float(fuse_mb))
        return cls(model, optimizer, mesh, **kw)

    # -- sharding policy ---------------------------------------------------
    def _setup_shardings(self):
        mesh = self.mesh
        sd = self.model.state_dict()
        shard_n = mesh.shape.get("sharding", 1)
        # backends without the pinned_host/device memory kinds (the CPU
        # runtime exposes only unpinned_host) fall back to plain
        # shardings: placement degenerates to device memory but every
        # numerical path is unchanged — what keeps offload parity
        # testable off-TPU
        from .offload_pipeline import supports_memory_kinds
        mk = supports_memory_kinds()

        def _host(ns):
            return NamedSharding(mesh, ns.spec,
                                 memory_kind="pinned_host") if mk else ns

        def _dev(ns):
            return NamedSharding(mesh, ns.spec,
                                 memory_kind="device") if mk else ns

        self._param_shardings = {}
        self._param_store_shardings = {}
        self._dev_param_shardings = {}
        # the PRE-ZeRO placement of each param (TP spec without the
        # stacked 'sharding' axis) — what a stage-3 all-gather restores
        # and the overlap plan's prefetch constrains to
        self._gather_shardings = {}
        for n in self._names:
            p = sd[n]
            spec = _current_spec(p.value)
            self._gather_shardings[n] = NamedSharding(mesh, P(*spec))
            # only matrix-shaped params join ZeRO-3: sharding 1-D params
            # (norm scales, biases) along the hidden dim makes GSPMD
            # propagate hidden-dim shardings into every activation that
            # touches them, forcing full-remat reshards; replicating
            # them costs ~nothing
            if self.stage >= 3 and shard_n > 1 and p.value.ndim >= 2:
                spec = _add_axis_to_spec(spec, "sharding",
                                         p.value.shape, shard_n, mesh)
            ns = NamedSharding(mesh, P(*spec))
            self._param_shardings[n] = ns
            self._param_store_shardings[n] = _host(ns) \
                if self.offload_params else ns
            self._dev_param_shardings[n] = _dev(ns)
            p._value = jax.device_put(p.value,
                                      self._param_store_shardings[n])
        self._opt_shardings = {}
        self._opt_store_shardings = {}
        self._dev_opt_shardings = {}
        for n in self._names:
            if self.stage >= 1 and shard_n > 1:
                p = sd[n]
                spec = _current_spec(p.value)
                if self.stage < 3:
                    spec = _add_axis_to_spec(spec, "sharding",
                                             p.value.shape, shard_n, mesh)
                ns = NamedSharding(mesh, P(*spec))
            else:
                ns = self._param_shardings[n]
            self._opt_shardings[n] = ns
            # storage placement: host when offloading, else == compute.
            # The explicit memory_kind="device" twin is what in-step
            # streaming transfers target — the transfer custom call must
            # carry BOTH placement and sharding or the SPMD partitioner
            # rejects it.
            self._opt_store_shardings[n] = _host(ns) \
                if self.offload else ns
            self._dev_opt_shardings[n] = _dev(ns)

    def _states_for_call(self):
        """Opt states as the compiled step expects them: host-resident
        (streaming mode) or transferred to device at the boundary (CPU
        fallback)."""
        if self.offload and not self._stream_offload:
            return [{k: jax.device_put(v, self._opt_shardings[n])
                     for k, v in st.items()}
                    for n, st in zip(self._names, self._opt_states)]
        return self._opt_states

    def _params_for_call(self, param_vals):
        """Param values as the compiled step expects them: host-parked
        (streaming mode handles transfers in-graph) or moved to device
        at the boundary (CPU fallback)."""
        if self.offload_params and not self._stream_offload:
            return [jax.device_put(v, self._param_shardings[n])
                    for n, v in zip(self._names, param_vals)]
        return param_vals

    def _park_params(self, new_params):
        """Updated params in their between-step storage placement."""
        if self.offload_params and not self._stream_offload:
            return [jax.device_put(v, self._param_store_shardings[n])
                    for n, v in zip(self._names, new_params)]
        return new_params

    def _park_states(self, new_states):
        """Return states in their between-step storage placement."""
        if self.offload and not self._stream_offload:
            return [{k: jax.device_put(v, self._opt_store_shardings[n])
                     for k, v in st.items()}
                    for n, st in zip(self._names, new_states)]
        return new_states

    def _shard_batch(self, arr):
        return shard_batch(self.mesh, arr, self.batch_axes,
                           self.seq_axis, self.seq_dim)

    # -- build -------------------------------------------------------------
    def _init_opt_states(self):
        from ..optimizer.jit_update import maybe_master_state
        sd = self.model.state_dict()
        opt = self.optimizer
        states = []
        for n in self._names:
            p = sd[n]
            if self.offload_params:
                # zeros_like/cast on a pinned_host array would try to
                # BUILD host-sharded arrays through the device path
                # (jax make_array_from_callback rejects the mix); init
                # from a device twin, the store device_put parks it
                p = Tensor(jax.device_put(
                    p.value, self._dev_param_shardings[n]))
            st = opt._init_state(p)
            # multi_precision: the fp32 master joins the state pytree and
            # is sharded by the same ZeRO policy as the moments
            st = maybe_master_state(opt, p, st)
            st = {k: jax.device_put(v, self._opt_store_shardings[n])
                  for k, v in st.items()}
            states.append(st)
        return states

    def _build(self):
        from ..jit import _swapped_state
        model = self.model
        opt = self.optimizer
        names = self._names
        buf_names = self._buf_names
        loss_fn = self.loss_fn
        hp = opt._hyper()
        upd = type(opt)._update
        sd = model.state_dict()
        wds, lr_scales = [], []
        for n in names:
            p = sd[n]
            wd = opt._wd_value(p)
            decay_fn = getattr(opt, "_apply_decay_param_fun", None)
            if decay_fn is not None and not decay_fn(p.name or n):
                wd = 0.0
            exclude_fn = getattr(opt, "_exclude_fn", None)
            if exclude_fn is not None and exclude_fn(p.name or n):
                wd = 0.0
            lr_ratio = getattr(opt, "_lr_ratio", None)
            lr_scales.append(float(lr_ratio(p)) if lr_ratio is not None
                             else 1.0)
            wds.append(wd)
        remat = self.remat

        # param offload streaming: block params (matching the stacked-
        # layer name pattern) stream inside their recompute regions via
        # the scope; the long tail (embeddings, lm_head, final norm)
        # transfers up-front in the forward
        import os
        stream_params = self.offload_params and self._stream_offload
        # PDTPU_PARAM_STREAM=1 opts into PER-BLOCK in-remat streaming
        # (HBM holds ~one block's params; see param_stream.py).  The
        # default is the boundary mode — all params transferred up-front
        # each step, grads/updates still host-resident — because the
        # current TPU toolchain ICEs on transfers inside rematerialized
        # regions ("Bitcast changes dimensionality" → with barriers,
        # "Unimplemented DMA from host to vmem"); measured 4.49B trains
        # at 550 tok/s on 16G in boundary mode (15.79G peak)
        per_block = os.environ.get("PDTPU_PARAM_STREAM", "0") == "1"
        from .offload_pipeline import BLOCK_STACK_PAT as block_pat
        # only matrix params stream: small 1-D scales would be DMA'd
        # host->vmem directly (unimplemented on the TPU runtime) and
        # cost nothing to keep device-resident
        streamed = [stream_params and per_block
                    and bool(block_pat.search(n))
                    and sd[n].value.ndim >= 2
                    for n in names]
        dev_param_sh = [self._dev_param_shardings[n] for n in names]
        from .param_stream import param_stream_scope
        stream_table = {id(sd[n]): dev_param_sh[i]
                        for i, n in enumerate(names) if streamed[i]}
        stream_names = {id(sd[n]): n
                        for i, n in enumerate(names) if streamed[i]}

        # comm/compute overlap (ISSUE 16): build the bucket plan once,
        # statically verify its cross-rank collective order BEFORE any
        # chip time, and swap the monolithic grad reduction for the
        # bucketed barrier-chained one.  Bit-exact vs the monolithic
        # path at grad_comm_dtype="auto" (tier-1-pinned).
        overlap_plan = None
        prefetch_on = False
        if self._comm_overlap and self.mesh.size > 1 \
                and not stream_params and not self.offload:
            from .comm_overlap import CommOverlapPlan
            plan = CommOverlapPlan.for_trainer(
                names, [tuple(sd[n].value.shape) for n in names],
                [str(sd[n].value.dtype) for n in names],
                self.mesh, self.stage,
                bucket_mb=self._comm_bucket_mb,
                comm_dtype=self._grad_comm_dtype,
                batch_axes=self.batch_axes)
            if plan.active:
                plan.verify()
                overlap_plan = plan
                prefetch_on = self.stage >= 3 \
                    and self.mesh.shape.get("sharding", 1) > 1
        self._overlap_plan = overlap_plan
        self._comm_profile = overlap_plan.comm_profile() \
            if overlap_plan is not None else None

        def loss_of(param_vals, buf_vals, key, batch):
            def fwd(param_vals):
                if overlap_plan is not None and prefetch_on:
                    # stage-3 param all-gather anchors, one bucket
                    # ahead in forward order (layout-neutral chain)
                    param_vals = overlap_plan.prefetch_params(
                        param_vals)
                if stream_params:
                    param_vals = [
                        v if streamed[i]
                        else jax.lax.optimization_barrier(
                            jax.device_put(v, dev_param_sh[i]))
                        for i, v in enumerate(param_vals)]
                sd_ = model.state_dict()
                with _swapped_state(model, names + buf_names,
                                    list(param_vals) + list(buf_vals)):
                    with prandom.key_scope(key), \
                         param_stream_scope(stream_table, stream_names), \
                         activation_sharding_scope(self.mesh,
                                                   self.batch_axes,
                                                   self.seq_axis,
                                                   self.seq_dim):
                        inputs = [Tensor(b) for b in batch[:-1]]
                        out = model(*inputs)
                        if loss_fn is not None:
                            loss = loss_fn(out, Tensor(batch[-1]))
                        else:
                            loss = model.compute_loss(out, Tensor(batch[-1]))
                    # capture buffer mutations (BN running stats etc.)
                    # before _swapped_state restores the originals
                    new_bufs = [sd_[n]._value for n in buf_names]
                return (loss._value if isinstance(loss, Tensor)
                        else loss), new_bufs
            if remat:
                fwd = jax.checkpoint(fwd)
            return fwd(param_vals)

        # stage 2 (ZeRO-2): force grads to MATERIALIZE sharded on the
        # 'sharding' axis — XLA must emit a reduce-scatter for the grad
        # reduction instead of an all-reduce (reference:
        # DygraphShardingOptimizerV2:585 / group_sharded_stage2.py grad
        # slicing).  Stage 1 keeps replicated grads (all-reduce) and only
        # shards optimizer state.
        grad_shardings = None
        if self.stage == 2 and self.mesh.shape.get("sharding", 1) > 1:
            grad_shardings = [self._opt_shardings[n] for n in names]

        from ..optimizer.jit_update import apply_update, apply_updates
        # single device: plain fused pallas update.  Sharded mesh: the
        # fused kernel is shard_map-wrapped over each state's spec inside
        # apply_update, so every chip updates only its ZeRO shard (a bare
        # pallas_call has no SPMD rule — GSPMD would replicate the state)
        fused_ok = self.mesh.size == 1
        mesh = self.mesh if self.mesh.size > 1 else None
        opt_specs = [self._opt_shardings[n].spec for n in names]

        offload = self._stream_offload
        dev_opt_sh = [self._dev_opt_shardings[n] for n in names]

        # param-offload scale: the latency-hiding scheduler HOISTS every
        # per-param state transfer to the front of the update phase,
        # making all masters+moments live in HBM at once (43G at 4.5B).
        # Chaining each param's transfers behind a previous param's
        # update output bounds the streaming window; the window size
        # trades transfer/compute overlap against peak HBM
        # (PDTPU_OFFLOAD_CHAIN_EVERY params per window, default 1).
        chain_updates = stream_params
        chain_every = max(1, int(os.environ.get(
            "PDTPU_OFFLOAD_CHAIN_EVERY", "1")))

        # nonfinite skip-step guard, compiled in ONLY when the flag is
        # on at build time — flags off, the step program is
        # bit-identical to the unguarded one (bench-asserted).  A bad
        # step (nonfinite loss OR grad-norm) keeps params, optimizer
        # state and buffers untouched; the host-side StepAnomalyGuard
        # bounds how many may run consecutively.
        from ..framework.flags import get_flag
        guard_on = bool(get_flag("skip_nonfinite_steps"))
        # numerics plane (ISSUE 14), same build-time contract as the
        # guard: off, the step program is byte-identical; on, the step
        # additionally returns per-layer-bundle norm scalars computed
        # from the grads/params it already holds
        from ..telemetry import numerics as _numerics
        numerics_on = self._numerics = _numerics.enabled()
        if numerics_on:
            self._num_bundles, num_assign = _numerics.bundles_of(names)

        def _numerics_stats(param_vals, grads, new_params):
            return _numerics.graph_stats(
                num_assign, len(self._num_bundles), param_vals, grads,
                new_params)

        def _finite_pred(loss, grads):
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in grads)
            return (jnp.isfinite(loss.astype(jnp.float32))
                    & jnp.isfinite(gsq))

        def _guarded(finite, new_tree, old_tree):
            return jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_tree, old_tree)

        def step(param_vals, opt_states, buf_vals, lr, step_i, key, batch):
            # the scopes are metadata alone (`op_name` in the HLO and in
            # a device trace): forward and backward are told apart by
            # what jax writes there itself, `jvp(..)`, `transpose(jvp(..))`
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals, buf_vals, key, batch)
            with jax.named_scope("train.grad_reduce"):
                if overlap_plan is not None:
                    # bucketed reduction: fused all-reduce (stage 0/1) or
                    # reduce-scatter (stage 2) per bucket, barrier-chained
                    # in reverse-topological issue order; stage 2
                    # re-applies the per-leaf sharded-grad constraint;
                    # stage 3 chains layout-neutrally (grad_shardings is
                    # None there — shard_map materializes the RS)
                    grads = overlap_plan.reduce_grads(
                        grads, self.mesh, leaf_shardings=grad_shardings)
                elif grad_shardings is not None:
                    grads = [jax.lax.with_sharding_constraint(g, gs)
                             for g, gs in zip(grads, grad_shardings)]
            with jax.named_scope("train.optimizer"):
                if fused_ok and not offload and not stream_params:
                    # single device, nothing host-resident: multi-tensor
                    # batching of the small params (see jit_update)
                    new_params, new_states = apply_updates(
                        upd, param_vals, grads, opt_states, lr, wds,
                        step_i, hp, lr_scales=lr_scales)
                else:
                    new_params, new_states = per_leaf_updates(
                        param_vals, grads, opt_states, lr, step_i)
            if numerics_on:
                # stats read the ATTEMPTED update (pre-guard selection):
                # a refused step still reports which layer's grad went
                # nonfinite
                nstats = _numerics_stats(param_vals, grads, new_params)
            if guard_on:
                with jax.named_scope("train.guard"):
                    ok = _finite_pred(loss, grads)
                    new_params = _guarded(ok, new_params, param_vals)
                    new_states = _guarded(ok, new_states, opt_states)
                    new_bufs = _guarded(ok, new_bufs, buf_vals)
            if numerics_on:
                return loss, new_params, new_states, new_bufs, nstats
            return loss, new_params, new_states, new_bufs

        def per_leaf_updates(param_vals, grads, opt_states, lr, step_i):
            new_params, new_states = [], []
            token = None
            for i, (p, g, s, wd, ls, sp) in enumerate(
                    zip(param_vals, grads, opt_states, wds, lr_scales,
                        opt_specs)):
                if offload:
                    # stream this param's state host->HBM; XLA overlaps
                    # the per-param transfers with the update chain
                    s = {k: jax.device_put(v, dev_opt_sh[i])
                         for k, v in s.items()}
                    if chain_updates and token is not None:
                        keys = list(s)
                        out = jax.lax.optimization_barrier(
                            tuple(s[k] for k in keys) + (token,))
                        s = dict(zip(keys, out[:-1]))
                if stream_params:
                    # param and grad are host-resident (grads of a
                    # host->device transfer land back on the host);
                    # bring this param's pair to HBM for the update —
                    # the out_shardings park the result back.  The
                    # barrier (a) forces an HBM materialization (an
                    # unbarriered copy fuses into the update kernel as
                    # an unimplemented host->vmem DMA) and (b) rides
                    # the same serialization chain as the states
                    p = jax.device_put(p, dev_param_sh[i])
                    g = jax.device_put(g, dev_param_sh[i])
                    if chain_updates and token is not None:
                        p, g, _ = jax.lax.optimization_barrier(
                            (p, g, token))
                    else:
                        p, g = jax.lax.optimization_barrier((p, g))
                np_, ns = apply_update(
                    upd, p, g, s, lr if ls == 1.0 else lr * ls, wd,
                    step_i, hp, fused_ok=fused_ok, mesh=mesh, spec=sp)
                new_params.append(np_)
                new_states.append(ns)
                if chain_updates and (i + 1) % chain_every == 0:
                    token = np_
            return new_params, new_states

        param_sh = [self._param_store_shardings[n] if stream_params
                    else self._param_shardings[n] for n in names]
        # outputs land back on the host only in streaming mode; the CPU
        # fallback parks them host-side at the call boundary instead
        out_opt = self._opt_store_shardings if self._stream_offload \
            else self._opt_shardings
        opt_sh = []
        for n, st in zip(names, self._opt_states):
            opt_sh.append({k: out_opt[n] for k in st})
        buf_sh = [None] * len(buf_names)
        donate = (0, 1, 2) if self._donate else ()
        self._step_fn = step
        self._out_shardings = (None, param_sh, opt_sh, buf_sh)
        if numerics_on:
            # the stats pytree is tiny per-bundle scalars — leave its
            # placement to XLA (None = unconstrained subtree)
            self._out_shardings = self._out_shardings + (None,)
        with self.mesh:
            self._compiled = jax.jit(
                step, donate_argnums=donate,
                out_shardings=self._out_shardings)
        # build-level sentinel (analysis.passes): structural passes over
        # the just-built artifacts — overlap-plan coherence, modeled
        # collective order.  Full-level (census/donation — an extra
        # compile) stays behind explicit .preflight().
        from ..analysis.passes import PassContext, sentinel_preflight
        sentinel_preflight(
            PassContext("trainer", self._sentinel_label(), engine=self,
                        mesh=self.mesh),
            level="build")

    def _sentinel_label(self) -> str:
        axes = "x".join(f"{a}{n}" for a, n in self.mesh.shape.items()
                        if n > 1) or "single"
        return f"trainer:stage{self.stage}:{axes}"

    def preflight(self, *batch, level: str = "full", manager=None,
                  census_min_bytes=None, census_slack=None):
        """Run the FULL static-sentinel catalog over this step's
        program (analysis.passes): the build-level structural passes
        plus donation aliasing, the HLO collective census diffed
        against the modeled CollectiveEvent schedule, and the
        replication audit.  Costs one extra lower+compile of the step
        — call it once per program shape (CI, tools/static_check.py,
        or before a long run), not per step.

        Returns a SentinelReport (None when FLAGS_static_sentinel is
        off); severity=error findings raise SentinelError."""
        from ..analysis.passes import PassContext, sentinel_preflight
        extra = {}
        if census_min_bytes is not None:
            extra["census_min_bytes"] = census_min_bytes
        if census_slack is not None:
            extra["census_slack"] = census_slack
        ctx = PassContext("trainer", self._sentinel_label(), engine=self,
                          args=batch, mesh=self.mesh, extra=extra)
        return sentinel_preflight(ctx, level=level, manager=manager)

    def compiled_hlo(self, *batch, optimized: bool = True) -> str:
        """Compile the step for `batch` (without executing) and return the
        HLO — lets tests and users assert the collective pattern their
        sharding stage implies.  optimized=False returns the pre-SPMD
        StableHLO, where explicit sharding constraints (e.g. stage-2 grad
        shardings) are still visible as @Sharding custom calls."""
        if self._pipeline is not None:
            return self._pipeline.compiled_hlo(*batch, optimized=optimized)
        args = self._trace_args(batch)   # builds self._compiled lazily
        lowered = self._compiled.lower(*args)
        return lowered.compile().as_text() if optimized \
            else lowered.as_text()

    def _trace_args(self, batch):
        """The one argument tuple every analysis entry point traces
        with (compiled_hlo / collective_schedule / lint) — a signature
        change to the step lands in all of them at once."""
        param_vals, buf_vals, batch_vals = self._prepare(batch)
        return (param_vals, self._states_for_call(), buf_vals,
                jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.int32),
                jax.random.key(0), batch_vals)

    def collective_schedule(self, *batch):
        """Ordered collective-event sequence of the traced train step
        (analysis.collectives) — psum/ppermute/all_gather/
        reduce_scatter eqns in program order.  SPMD traces one program
        for the whole mesh, so every rank shares this schedule; pass
        `{rank: step.collective_schedule(*batch) for rank in ...}` to
        `check_collective_order` when composing with per-rank host
        logic (the PipelineEngine builds its own per-stage lists).
        A live telemetry sink receives the per-kind counts as a
        `collective.schedule` event."""
        if self._pipeline is not None:
            return self._pipeline.collective_schedule(*batch)
        from ..analysis.collectives import collective_schedule
        args = self._trace_args(batch)
        with self.mesh:
            events = collective_schedule(self._compiled, *args)
        from .. import telemetry as _tel
        if _tel.active():
            kinds = {}
            for e in events:
                kinds[e.kind] = kinds.get(e.kind, 0) + 1
            _tel.emit("collective.schedule", trainer="sharded",
                      total=len(events), kinds=kinds)
        return events

    def overlap_schedule(self):
        """The comm-overlap plan's static per-rank event lists
        ({rank: [CollectiveEvent, ...]}), or None when overlap is off —
        what `assert_collective_order` proves identical across the
        mesh before any chip time (the plan already ran the proof at
        build; this re-exposes it for composition with pipeline
        schedules)."""
        if self._compiled is None and self._overlap_plan is None:
            # plan is built with the step; force it without running
            if self._opt_states is None:
                self._opt_states = self._init_opt_states()
            self._build()
        plan = self._overlap_plan
        return plan.schedules() if plan is not None else None

    def lint_comm_dtype(self, *batch):
        """Satellite-1 audit (analysis.lints.lint_grad_comm_dtype):
        jaxpr proof that every fused grad bucket's collective runs at
        the plan's requested wire width — a bf16 grad silently upcast
        to fp32 before the reduce (doubling comm bytes) is a finding.
        Empty list when overlap is off (nothing fused to audit)."""
        args = self._trace_args(batch)
        if self._overlap_plan is None:
            return []
        from ..analysis.lints import lint_grad_comm_dtype
        with self.mesh:
            return lint_grad_comm_dtype(self._compiled, *args,
                                        plan=self._overlap_plan)

    def lint(self, *batch, dtype: bool = False,
             transfers: Optional[bool] = None, donation: bool = True,
             logits: bool = False):
        """Run the analysis lints over the traced+lowered train step.
        Returns {category: [Finding, ...]}.

        transfers: device_put eqns inside the step — a silent per-step
          copy.  Default (None) = on for plain steps, off when offload
          streaming is the design; pass an explicit bool to override
          (True audits the streaming structure itself).  donation:
          donated buffers the lowered module did not alias.  dtype:
          off by default — AMP loss upcasts are intentional fp32; turn
          on to audit a step that should be uniformly low-precision.
          logits: lint_materialized_logits with the model config's
          vocab_size — the fused-CE (FLAGS_fused_ce) contract that no
          [B, S, vocab] fp32 buffer exists anywhere in the step."""
        if self._pipeline is not None:
            kw = {"dtype": dtype, "donation": donation}
            if transfers is not None:    # explicit override passes down
                kw["transfers"] = transfers
            return self._pipeline.lint(*batch, **kw)
        from ..analysis.lints import lint_compiled_step
        if transfers is None:
            transfers = not (self.offload or self.offload_params)
        logits_vocab = None
        logits_min_rows = None
        if logits:
            logits_vocab = int(getattr(
                getattr(self.model, "config", None), "vocab_size", 0)) \
                or None
        if logits_vocab and batch:
            # also flag FLATTENED [B*S, V] fp32 buffers — but only when
            # the token count exceeds the fused path's row chunk, below
            # which a full [tokens, V] chunk slice is legitimate (the
            # chunking is vacuous at that size)
            from ..ops.pallas.fused_cross_entropy import _DEFAULT_CHUNK
            import numpy as _np
            tokens = int(_np.prod(batch[0].shape)) if batch[0].shape \
                else 0
            # gate AND threshold both use the post-shift row count (the
            # causal loss drops one position per sequence): armed only
            # when the fused path actually chunks, so its own
            # [_DEFAULT_CHUNK, V] slice can never reach min_rows
            shifted = tokens - int(batch[0].shape[0])
            if shifted > _DEFAULT_CHUNK:
                logits_min_rows = shifted
        args = self._trace_args(batch)
        return lint_compiled_step(
            self._compiled, args, mesh=self.mesh, dtype=dtype,
            transfers=transfers, donation=donation and self._donate,
            logits_vocab=logits_vocab, logits_min_rows=logits_min_rows)

    def _prepare(self, batch):
        """Shared prologue of __call__ and compiled_hlo: gather current
        values, lazily init opt states / build, shard the batch."""
        sd = self._sd = self.model.state_dict()
        param_vals = self._params_for_call(
            [sd[n]._value for n in self._names])
        buf_vals = [sd[n]._value for n in self._buf_names]
        if self._opt_states is None:
            self._opt_states = self._init_opt_states()
        if self._compiled is None:
            self._build()
        batch_vals = tuple(
            self._shard_batch(b.value if isinstance(b, Tensor)
                              else jnp.asarray(b)) for b in batch)
        return param_vals, buf_vals, batch_vals

    def _build_multi(self):
        """K sharded steps fused into one device program via lax.scan
        (host-loop elision — see jit.TrainStep._build_multi)."""
        step = self._step_fn
        stream = self._stream_offload
        numerics_on = getattr(self, "_numerics", False)
        dev_opt_sh = [self._dev_opt_shardings[n] for n in self._names]

        def multi(param_vals, opt_states, buf_vals, lrs, step0, key,
                  stacked):
            if stream:
                # bring the host-parked states to HBM ONCE for the whole
                # fused window (a host-resident scan carry would ping-
                # pong memory spaces every inner step); the final
                # out_shardings park them back on the host
                opt_states = [
                    {k: jax.device_put(v, dev_opt_sh[i])
                     for k, v in st.items()}
                    for i, st in enumerate(opt_states)]

            def body(carry, xs):
                params, states, bufs, i = carry
                k = jax.random.fold_in(key, i)
                out = step(
                    params, states, bufs, lrs[i], step0 + i, k, xs)
                if numerics_on:
                    loss, params, states, bufs, nstats = out
                    return (params, states, bufs, i + 1), (loss, nstats)
                loss, params, states, bufs = out
                return (params, states, bufs, i + 1), loss
            init = (list(param_vals), opt_states, list(buf_vals),
                    jnp.asarray(0, jnp.int32))
            (params, states, bufs, _), ys = jax.lax.scan(
                body, init, stacked)
            if numerics_on:
                losses, nstats = ys
                return losses, params, states, bufs, nstats
            return ys, params, states, bufs

        donate = (0, 1, 2) if self._donate else ()
        with self.mesh:
            self._compiled_multi = jax.jit(
                multi, donate_argnums=donate,
                out_shardings=self._out_shardings)

    def run_steps(self, *stacked_batch, advance_lr_scheduler=True):
        """Run K sharded train steps in one compiled call; each batch
        array carries a leading K dim.  Returns the [K] loss Tensor.
        A per-step LRScheduler is advanced inside the window (see
        jit.per_step_lrs); epoch-granular schedulers pass
        advance_lr_scheduler=False."""
        if self._pipeline is not None:
            return self._pipeline.run_steps(
                *stacked_batch, advance_lr_scheduler=advance_lr_scheduler)
        from .. import telemetry as _tel
        from ..distributed.watchdog import watched
        from ..jit import per_step_lrs
        from ..telemetry import compile_cache as _cc, memledger as _ml
        with _tel.span("train.step") as call:
            with _tel.span("train.prepare"):
                param_vals, buf_vals, _ = self._prepare(
                    tuple(Tensor(b.value[0] if isinstance(b, Tensor)
                                 else jnp.asarray(b)[0])
                          for b in stacked_batch))
                if getattr(self, "_compiled_multi", None) is None:
                    self._build_multi()
                stacked = self._step_faults(tuple(
                    self._stack_shard(b.value if isinstance(b, Tensor)
                                      else jnp.asarray(b))
                    for b in stacked_batch))
                k = int(stacked[0].shape[0])
                call.set(step=self.optimizer._step_count + k, k=k)
                lrs, commit_lr = per_step_lrs(self.optimizer, k,
                                              advance=advance_lr_scheduler)
                step0 = jnp.asarray(self.optimizer._step_count + 1,
                                    jnp.int32)
                key = prandom.next_key()
                args = (param_vals, self._states_for_call(), buf_vals, lrs,
                        step0, key, stacked)
                # ledger registration BEFORE aot_for: an armed AOT compile
                # then overwrites the pending provider with free measured
                # stats
                _ml.note_jit(self, "multi", self._compiled_multi, args,
                             f"ShardedTrainStep.multi.s{self.stage}",
                             mesh=self.mesh,
                             sig=tuple(b.shape for b in stacked))
                if self._comm_profile is not None:
                    # (re)attach the grad-comm profile — registration above
                    # clears per-program cost state, and the profile is a
                    # build-time property of THIS program
                    from ..telemetry import costledger as _cl
                    _cl.note_comm(f"ShardedTrainStep.multi.s{self.stage}",
                                  self._comm_profile)
                fn = _cc.aot_for(self._aot, "multi", self._compiled_multi,
                                 args, stacked,
                                 f"ShardedTrainStep.multi.s{self.stage}",
                                 mesh=self.mesh)
            _tel.counter("train.steps").inc(k)   # lifetime total, sink or not
            tel_on = _tel.active()
            t0 = time.perf_counter()
            with _tel.span("train.dispatch"), \
                    watched(f"sharded train run_steps(k={k})"):
                out = fn(*args)
                if getattr(self, "_numerics", False):
                    losses, new_params, new_states, new_bufs, nstats = out
                else:
                    (losses, new_params, new_states, new_bufs), nstats = \
                        out, None
                if tel_on and _tel.config("sync_steps"):
                    jax.block_until_ready(losses)
            wall_ms = (time.perf_counter() - t0) * 1e3
            with _tel.span("train.writeback"):
                commit_lr()
                self.optimizer._step_count += k
                sd = self._sd
                for n, v in zip(self._names, self._park_params(new_params)):
                    sd[n]._value = v
                for n, v in zip(self._buf_names, new_bufs):
                    sd[n]._value = v
                self._opt_states = self._park_states(new_states)
                bad_layer = None
                if nstats is not None:
                    from ..telemetry import numerics as _numerics
                    bad_layer = _numerics.record(
                        "sharded", self.optimizer._step_count, k,
                        self._num_bundles, nstats,
                        extra={"stage": self.stage})
                self._guard_record(losses, layer=bad_layer)
            if tel_on:
                _tel.step_event(self, label="sharded", kind="multi",
                                step=self.optimizer._step_count, k=k,
                                wall_ms=wall_ms,
                                batch_vals=tuple(b[0] for b in stacked),
                                extra={"stage": self.stage}, span=call)
        return Tensor(losses)

    def _stack_shard(self, arr):
        """Shard a [K, batch, ...] stack on dim 1 (the batch dim of each
        step)."""
        from ..distributed.topology import batch_partition_spec
        spec = batch_partition_spec(self.mesh, arr.shape[1:],
                                    self.batch_axes)
        return jax.device_put(
            arr, NamedSharding(self.mesh, P(None, *spec)))

    def sync_to_model(self):
        """Streamed-pipeline mode: write the authoritative host stacks
        back into the model's per-layer Tensors (do this before
        checkpointing or eval through the module API).  No-op for the
        non-stream paths, whose __call__ already keeps the model
        current."""
        if self._pipeline is not None:
            self._pipeline.sync_to_model()

    # -- fault tolerance ---------------------------------------------------
    def attach_data_cursor(self, cursor):
        """Attach an io.ElasticDataCursor so checkpoints carry the
        topology-independent (epoch, global_sample_offset) beside the
        arrays — a resume at a different dp degree replays exactly the
        unseen samples."""
        if self._pipeline is not None:
            self._pipeline.attach_data_cursor(cursor)
        self._data_cursor = cursor

    def train_state(self):
        """(arrays, meta) of the FULL training state: model params and
        buffers, per-param optimizer state, global step, LR scheduler,
        process RNG and any attached data cursor — everything a
        bit-exact resume needs (N steps ≡ N/2 + save +
        restore-into-fresh-state + N/2).  Feed to
        `distributed.checkpoint.save_train_checkpoint`."""
        if self._pipeline is not None:
            return self._pipeline.train_state()
        from ..distributed.checkpoint import optimizer_meta, cursor_to_meta
        sd = self.model.state_dict()
        if self._opt_states is None:
            self._opt_states = self._init_opt_states()
        arrays = {f"model.{n}": sd[n]._value for n in sd}
        for n, st in zip(self._names, self._opt_states):
            for k, v in st.items():
                arrays[f"opt.{n}.{k}"] = v
        return arrays, cursor_to_meta(self, optimizer_meta(self.optimizer))

    def load_train_state(self, arrays, meta):
        if self._pipeline is not None:
            return self._pipeline.load_train_state(arrays, meta)
        from ..distributed.checkpoint import (apply_optimizer_meta,
                                              cursor_from_meta)
        sd = self.model.state_dict()
        for n in sd:
            if f"model.{n}" in arrays:
                sd[n]._value = arrays[f"model.{n}"]
        if self._opt_states is None:
            self._opt_states = self._init_opt_states()
        for n, st in zip(self._names, self._opt_states):
            for k in st:
                if f"opt.{n}.{k}" in arrays:
                    st[k] = arrays[f"opt.{n}.{k}"]
        apply_optimizer_meta(self.optimizer, meta)
        cursor_from_meta(self, meta)

    def _step_faults(self, batch_vals):
        """Thread the train-step injection points: `step.begin`
        (kill/error/delay) and `step.data` (mode=nan poisons the first
        float batch array — the deterministic way to make THIS step's
        loss and grads genuinely nonfinite for guard tests)."""
        from ..jit import _step_faults
        return tuple(_step_faults(batch_vals, "sharded"))

    def _guard_record(self, loss, layer=None):
        """Host half of the skip-step path: budget consecutive bad
        steps, back off the attached GradScaler.  Only consulted when
        FLAGS_skip_nonfinite_steps is on (it forces a host sync on the
        loss — never on the flags-off hot path).  `layer` is the
        numerics plane's first-nonfinite attribution — the abort
        report then names where the divergence started."""
        from ..framework.flags import get_flag
        if not get_flag("skip_nonfinite_steps"):
            return
        if self._guard is None:
            from ..distributed.guard import StepAnomalyGuard
            self._guard = StepAnomalyGuard(scaler=self._scaler,
                                           name="sharded train step")
        for v in np.atleast_1d(np.asarray(loss)):
            self._guard.record(float(v), step=self.optimizer._step_count,
                               layer=layer)

    # -- run ---------------------------------------------------------------
    def __call__(self, *batch):
        from ..distributed.watchdog import watched
        if self._pipeline is not None:
            return self._pipeline(*batch)
        from .. import telemetry as _tel
        from ..telemetry import compile_cache as _cc, memledger as _ml
        with _tel.span("train.step", step=self.optimizer._step_count + 1,
                       k=1) as call:
            with _tel.span("train.prepare"):
                param_vals, buf_vals, batch_vals = self._prepare(batch)
                batch_vals = self._step_faults(batch_vals)
                sd = self._sd
                self.optimizer._step_count += 1
                lr = self.optimizer.get_lr()
                key = prandom.next_key()
                args = (param_vals, self._states_for_call(), buf_vals,
                        jnp.asarray(lr, jnp.float32),
                        jnp.asarray(self.optimizer._step_count, jnp.int32),
                        key, batch_vals)
                _ml.note_jit(self, "step", self._compiled, args,
                             f"ShardedTrainStep.step.s{self.stage}",
                             mesh=self.mesh,
                             sig=tuple(b.shape for b in batch_vals))
                if self._comm_profile is not None:
                    from ..telemetry import costledger as _cl
                    _cl.note_comm(f"ShardedTrainStep.step.s{self.stage}",
                                  self._comm_profile)
                fn = _cc.aot_for(self._aot, "step", self._compiled, args,
                                 batch_vals,
                                 f"ShardedTrainStep.step.s{self.stage}",
                                 mesh=self.mesh)
            _tel.counter("train.steps").inc()    # lifetime total, sink or not
            tel_on = _tel.active()
            t0 = time.perf_counter()
            with _tel.span("train.dispatch"), watched("sharded train step"):
                out = fn(*args)
                if getattr(self, "_numerics", False):
                    loss, new_params, new_states, new_bufs, nstats = out
                else:
                    (loss, new_params, new_states, new_bufs), nstats = \
                        out, None
                if tel_on and _tel.config("sync_steps"):
                    jax.block_until_ready(loss)
            wall_ms = (time.perf_counter() - t0) * 1e3
            with _tel.span("train.writeback"):
                for n, v in zip(self._names, self._park_params(new_params)):
                    sd[n]._value = v
                for n, v in zip(self._buf_names, new_bufs):
                    sd[n]._value = v
                self._opt_states = self._park_states(new_states)
                bad_layer = None
                if nstats is not None:
                    from ..telemetry import numerics as _numerics
                    bad_layer = _numerics.record(
                        "sharded", self.optimizer._step_count, 1,
                        self._num_bundles, nstats,
                        extra={"stage": self.stage})
                self._guard_record(loss, layer=bad_layer)
            if tel_on:
                _tel.step_event(self, label="sharded", kind="step",
                                step=self.optimizer._step_count, k=1,
                                wall_ms=wall_ms, batch_vals=batch_vals,
                                extra={"stage": self.stage}, span=call)
        return Tensor(loss)
