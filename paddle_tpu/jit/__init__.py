"""paddle_tpu.jit — the compiled execution path.

Reference: `python/paddle/jit/` (to_static api.py:195, SOT bytecode JIT,
dy2static AST transforms) + the C++ executor stack (`fluid/framework/
new_executor/`) it feeds.

TPU-native redesign: Python tracing IS the native staging mechanism — the
whole SOT/AST machinery collapses into `jax.jit` over a functionalized
Layer.  `functional_call` swaps parameters/buffers for traced values so the
SAME Layer object serves eager and compiled execution; `TrainStep` fuses
forward+backward+optimizer into one XLA executable with donated buffers
(replacing the interpreter + GC of the reference's executor with XLA's
static buffer plan).
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from ..ops.pallas._x64 import x64_off

from ..framework.tensor import Tensor, Parameter
from ..framework.tape import no_grad
from ..framework import random as prandom
from ..framework import dtypes

__all__ = ["to_static", "not_to_static", "functional_call", "TrainStep",
           "save", "load", "ignore_module", "enable_to_static"]

_to_static_enabled = True


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


@contextlib.contextmanager
def _swapped_state(layer, names, values):
    """Temporarily replace named parameters/buffers of `layer` (and
    sublayers) with `values` (jax arrays or tracers).  While active,
    in-place buffer mutation under tracing is SAFE (any tracer written
    into a buffer is either captured by the trainer or restored away),
    so batch_norm et al. consult `in_swapped_state()` before mutating
    running stats with traced values."""
    global _SWAP_DEPTH
    sd = layer.state_dict()
    originals = []
    for n, v in zip(names, values):
        t = sd[n]
        originals.append((t, t._value))
        t._value = v if not isinstance(v, Tensor) else v._value
    _SWAP_DEPTH += 1
    try:
        yield
    finally:
        _SWAP_DEPTH -= 1
        for t, v in originals:
            t._value = v


_SWAP_DEPTH = 0


def in_swapped_state() -> bool:
    return _SWAP_DEPTH > 0


def functional_call(layer, state: Dict[str, Any], *args, **kwargs):
    """Run `layer(*args)` with parameters/buffers taken from `state`.
    Pure w.r.t. `state` → composes with jax.jit/grad/vmap."""
    names = list(state.keys())
    values = [state[n] for n in names]
    with _swapped_state(layer, names, values):
        return layer(*args, **kwargs)


def _leaves_to_values(tree):
    return jax.tree_util.tree_map(
        lambda x: x._value if isinstance(x, Tensor) else x, tree,
        is_leaf=lambda x: isinstance(x, Tensor))


class StaticFunction:
    """Result of @to_static on a function or Layer method.

    Parameters/buffers are hoisted to explicit jit arguments (keeps the
    executable valid across optimizer updates — the reference analog is the
    parameter scope passed to the program, not baked into it).
    """

    def __init__(self, fn, layer=None, input_spec=None, backend=None,
                 **kwargs):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        self._compiled = None
        self._names = None
        self._fallback = False   # SOT-style graph break: run eager

    def _build(self):
        layer = self._layer
        # dy2static AST pass: tensor-dependent if/while lower to
        # lax.cond/while_loop (reference: jit/dy2static transformers);
        # anything it can't convert keeps Python semantics and, if a
        # tracer then hits a Python branch, the call GRAPH-BREAKS to
        # eager below (reference: SOT fallback, jit/sot/translate.py)
        from .dy2static import ast_transform
        fn = ast_transform(self._fn)

        if layer is not None:
            names = list(layer.state_dict().keys())
            self._names = names

            def raw(state_vals, *in_vals):
                with _swapped_state(layer, names, state_vals):
                    out = fn(*[Tensor(v) if isinstance(v, jax.Array)
                               else v for v in in_vals])
                return _leaves_to_values(out)
            self._compiled = jax.jit(raw)
        else:
            def raw(*in_vals):
                return _leaves_to_values(fn(*in_vals))
            self._compiled = jax.jit(raw)

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled or self._fallback:
            return self._fn(*args, **kwargs)
        if kwargs:
            # keyword args force eager fallback (graph-break analog)
            return self._fn(*args, **kwargs)
        first_call = self._compiled is None
        if first_call:
            self._build()
        try:
            if self._layer is not None:
                sd = self._layer.state_dict()
                state_vals = [sd[n]._value for n in self._names]
                out = self._compiled(state_vals, *args)
            else:
                out = self._compiled(*args)
        except Exception as e:  # noqa: BLE001 — SOT-style graph break
            # Tracer concretization errors are always a graph break.
            # On the FIRST call (trace+compile), ANY failure falls back
            # to eager (the transform's restrictions — branch pytree
            # mismatch, lax.cond TypeError, a synthesized NameError —
            # surface here; eager either succeeds or raises the true
            # user error).  After a successful compile, non-tracer
            # errors are real runtime failures and propagate.
            tracer_err = isinstance(e, jax.errors.ConcretizationTypeError)
            if not tracer_err and not first_call:
                raise
            import warnings
            warnings.warn(
                f"to_static: graph break in "
                f"{getattr(self._fn, '__qualname__', self._fn)} "
                f"({type(e).__name__}: {e}); falling back to eager "
                "execution", RuntimeWarning)
            self._fallback = True
            return self._fn(*args, **kwargs)
        return jax.tree_util.tree_map(
            lambda x: Tensor(x) if isinstance(x, jax.Array) else x, out)

    @property
    def forward_function(self):
        return self._fn

    def concrete_program_specify_input_spec(self, *a, **k):
        return None


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Reference: jit/api.py:195.  Works as decorator or wrapper on a
    function or a Layer (wrapping its forward)."""
    from ..nn import Layer

    def decorate(obj):
        if isinstance(obj, Layer):
            sf = StaticFunction(obj.forward, layer=obj,
                               input_spec=input_spec)
            obj.forward = sf
            return obj
        return StaticFunction(obj, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# ---------------------------------------------------------------------------
# TrainStep — whole-step compilation (the perf path used by Model.fit
# and the distributed trainer).
# ---------------------------------------------------------------------------
def per_step_lrs(optimizer, k: int, advance: bool = True):
    """Per-step LR array [k] for a fused run_steps window, plus a
    commit callback.

    With ``advance`` (the default), an attached LRScheduler is treated
    as PER-STEP and advanced k times — the host loop it would normally
    be stepped in is fused into the device scan, so the trainer owns
    the advance; callers must NOT also call scheduler.step() for those
    k steps.  Epoch-granular schedulers (e.g. hapi's
    LRScheduler(by_epoch=True) callback) must pass
    ``advance_lr_scheduler=False`` to run_steps: the LR is then held at
    its current value for the window and the caller keeps stepping the
    scheduler at epoch boundaries as before.

    The scheduler is NOT mutated here: the k values are computed on a
    rolled-back state and the advance is applied by the returned
    ``commit()`` — call it only after the device step succeeds, so a
    trace/compile/OOM failure leaves the schedule aligned with
    optimizer._step_count."""
    sched = getattr(optimizer, "_learning_rate_scheduler", None)
    if sched is None or not advance:
        return (jnp.full((k,), float(optimizer.get_lr()), jnp.float32),
                lambda: None)
    snap = dict(sched.state_dict())
    lrs = []
    for _ in range(k):
        lrs.append(float(sched()))
        sched.step()
    advanced = dict(sched.state_dict())
    sched.set_state_dict(snap)

    def commit():
        sched.set_state_dict(advanced)
    return jnp.asarray(lrs, jnp.float32), commit


def _step_faults(batch_vals, where):
    """Train-step fault-injection boundary (distributed.fault):
    `step.begin` handles kill/error/delay itself; mode=nan at EITHER
    point poisons the first float batch array so THIS step's loss and
    grads go genuinely nonfinite (the deterministic NaN-step harness —
    `step.begin:mode=nan` and `step.data:mode=nan` are equivalent
    plants; step.begin used to swallow data modes silently)."""
    from ..distributed import fault
    if not fault.is_active():
        return batch_vals
    f = fault.hit("step.begin", key=where)
    if f is None or f.mode != "nan":
        f = fault.hit("step.data", key=where)
    if f is not None and f.mode == "nan":
        batch_vals = list(batch_vals)
        for i, b in enumerate(batch_vals):
            if jnp.issubdtype(b.dtype, jnp.inexact):
                batch_vals[i] = jnp.full_like(b, jnp.nan)
                break
    return batch_vals


class TrainStep:
    """Fused forward+backward+update as ONE jitted function with donated
    param/opt-state buffers.

    Replaces the reference's per-op eager loop + EagerReducer + optimizer
    kernels.  Under a mesh, pass `in_shardings` for params/opt-state/batch
    and XLA GSPMD inserts all collectives (dp grad psum = the reference's
    fused_allreduce_gradients; sharding axes = GroupSharded stages).
    """

    def __init__(self, model, loss_fn, optimizer, mesh=None,
                 param_sharding=None, data_sharding=None, donate=True,
                 rematerialize=False):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self._names = [n for n, _ in model.named_parameters()]
        self._buf_names = [n for n in model.state_dict()
                           if n not in self._names]
        self._donate = donate
        self._remat = rematerialize
        self._compiled = None
        self._opt_states = None
        # AOT executable store (telemetry.compile_cache): populated only
        # while FLAGS_compile_cache_dir is armed; keyed by batch aval
        # signature so a shape change falls back to the retracing jit
        self._aot: Dict[Any, Any] = {}

    def _init_opt_states(self, params):
        from ..optimizer.jit_update import maybe_master_state
        opt = self.optimizer
        sd = self.model.state_dict()
        states = []
        for n in self._names:
            st = opt._init_state(sd[n])
            states.append(maybe_master_state(opt, sd[n], st))
        return states

    def _build(self, sample_args):
        model = self.model
        opt = self.optimizer
        names = self._names
        buf_names = self._buf_names
        loss_fn = self.loss_fn
        hp = opt._hyper()
        upd = type(opt)._update
        wds = []
        sd = model.state_dict()
        for n in names:
            p = sd[n]
            wd = opt._wd_value(p)
            decay_fn = getattr(opt, "_apply_decay_param_fun", None)
            if decay_fn is not None and not decay_fn(p.name or n):
                wd = 0.0
            wds.append(wd)
        remat = self._remat
        # numerics plane (ISSUE 14): compiled in only when the flag is
        # on at build time — flags off, the step program is
        # byte-identical to an unflagged build (bench-asserted)
        from ..telemetry import numerics as _numerics
        numerics_on = self._numerics = _numerics.enabled()
        if numerics_on:
            self._num_bundles, num_assign = _numerics.bundles_of(names)

        def loss_of(param_vals, buf_vals, key, *batch):
            def fwd(param_vals):
                sd_ = model.state_dict()
                with _swapped_state(model, names + buf_names,
                                    list(param_vals) + list(buf_vals)):
                    with prandom.key_scope(key):
                        out = model(*[Tensor(b) for b in batch[:-1]])
                        loss = loss_fn(out, Tensor(batch[-1]))
                    # capture buffer mutations (BN running stats etc.)
                    # BEFORE _swapped_state restores the originals — the
                    # step threads them out functionally
                    new_bufs = [sd_[n]._value for n in buf_names]
                return (loss._value if isinstance(loss, Tensor)
                        else loss), new_bufs
            if remat:
                fwd = jax.checkpoint(fwd)
            return fwd(param_vals)

        from ..optimizer.jit_update import apply_updates

        def step(param_vals, opt_states, buf_vals, lr, step_i, key, *batch):
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals, buf_vals, key, *batch)
            new_params, new_states = apply_updates(
                upd, param_vals, grads, opt_states, lr, wds, step_i, hp)
            if numerics_on:
                nstats = _numerics.graph_stats(
                    num_assign, len(self._num_bundles), param_vals,
                    grads, new_params)
                return loss, new_params, new_states, new_bufs, nstats
            return loss, new_params, new_states, new_bufs

        self._step_fn = step
        donate = (0, 1, 2) if self._donate else ()
        self._compiled = jax.jit(step, donate_argnums=donate)

    def _build_multi(self):
        """K optimizer steps fused into ONE device program via lax.scan —
        host-loop elision: per-step dispatch latency is paid once per K
        steps (what it costs on today's chip: not measured).  The learning
        rate is a scanned [K] array (per-step schedulers advance inside
        the fused window); step_i advances inside the scan so Adam bias
        correction stays exact."""
        step = self._step_fn
        numerics_on = getattr(self, "_numerics", False)

        def multi(param_vals, opt_states, buf_vals, lrs, step0, key,
                  *stacked):
            def body(carry, xs):
                params, states, bufs, i = carry
                k = jax.random.fold_in(key, i)
                out = step(
                    params, states, bufs, lrs[i], step0 + i, k, *xs)
                if numerics_on:
                    loss, params, states, bufs, nstats = out
                    return (params, states, bufs, i + 1), (loss, nstats)
                loss, params, states, bufs = out
                return (params, states, bufs, i + 1), loss
            init = (list(param_vals), opt_states, list(buf_vals),
                    jnp.asarray(0, jnp.int32))
            (params, states, bufs, _), ys = jax.lax.scan(
                body, init, tuple(stacked))
            if numerics_on:
                losses, nstats = ys
                return losses, params, states, bufs, nstats
            return ys, params, states, bufs

        donate = (0, 1, 2) if self._donate else ()
        self._compiled_multi = jax.jit(multi, donate_argnums=donate)

    def run_steps(self, *stacked_batch, advance_lr_scheduler=True):
        """Run K train steps in one compiled call.  stacked_batch:
        (*inputs, labels) arrays each with a leading K (steps) dim;
        returns the per-step loss Tensor of shape [K].  A per-step
        LRScheduler is advanced inside the window (see per_step_lrs);
        epoch-granular schedulers pass advance_lr_scheduler=False."""
        model = self.model
        sd = model.state_dict()
        param_vals = [sd[n]._value for n in self._names]
        buf_vals = [sd[n]._value for n in self._buf_names]
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in stacked_batch]
        batch_vals = _step_faults(batch_vals, "jit-multi")
        if self._opt_states is None:
            self._opt_states = self._init_opt_states(param_vals)
        if self._compiled is None:
            self._build(tuple(b[0] for b in batch_vals))
        if getattr(self, "_compiled_multi", None) is None:
            self._build_multi()
        k = int(batch_vals[0].shape[0])
        lrs, commit_lr = per_step_lrs(self.optimizer, k,
                                      advance=advance_lr_scheduler)
        step0 = jnp.asarray(self.optimizer._step_count + 1, jnp.int32)
        key = prandom.next_key()
        args = (param_vals, self._opt_states, buf_vals, lrs, step0, key,
                *batch_vals)
        from ..telemetry import compile_cache as _cc, memledger as _ml
        # ledger registration BEFORE aot_for: an armed AOT compile then
        # overwrites the pending provider with free measured stats
        _ml.note_jit(self, "multi", self._compiled_multi, args,
                     "jit.TrainStep.multi",
                     sig=tuple(b.shape for b in batch_vals))
        fn = _cc.aot_for(self._aot, "multi", self._compiled_multi, args,
                         batch_vals, "jit.TrainStep.multi")
        from .. import telemetry as _tel
        _tel.counter("train.steps").inc(k)   # lifetime total, sink or not
        tel_on = _tel.active()
        t0 = time.perf_counter()
        out = fn(*args)
        if getattr(self, "_numerics", False):
            losses, new_params, new_states, new_bufs, nstats = out
        else:
            (losses, new_params, new_states, new_bufs), nstats = out, None
        if tel_on and _tel.config("sync_steps"):
            jax.block_until_ready(losses)
        wall_ms = (time.perf_counter() - t0) * 1e3
        commit_lr()
        self.optimizer._step_count += k
        for n, v in zip(self._names, new_params):
            sd[n]._value = v
        for n, v in zip(self._buf_names, new_bufs):
            sd[n]._value = v
        self._opt_states = new_states
        if tel_on:
            _tel.step_event(self, label="jit", kind="multi",
                            step=self.optimizer._step_count, k=k,
                            wall_ms=wall_ms,
                            batch_vals=tuple(b[0] for b in batch_vals))
        if nstats is not None:
            from ..telemetry import numerics as _numerics
            _numerics.record("jit", self.optimizer._step_count, k,
                             self._num_bundles, nstats)
        return Tensor(losses)

    def attach_data_cursor(self, cursor):
        """Attach an io.ElasticDataCursor: its (epoch, offset) rides
        train_state meta so checkpoints carry the topology-independent
        data position beside params/opt state."""
        self._data_cursor = cursor

    def train_state(self):
        """(arrays, meta) of the full training state — params, buffers,
        optimizer state, global step, LR scheduler, RNG, attached data
        cursor — for `distributed.checkpoint.save_train_checkpoint`
        (same contract as ShardedTrainStep.train_state; the resume is
        bit-exact)."""
        from ..distributed.checkpoint import optimizer_meta, cursor_to_meta
        sd = self.model.state_dict()
        if self._opt_states is None:
            self._opt_states = self._init_opt_states(
                [sd[n]._value for n in self._names])
        arrays = {f"model.{n}": sd[n]._value for n in sd}
        for n, st in zip(self._names, self._opt_states):
            for k, v in st.items():
                arrays[f"opt.{n}.{k}"] = v
        return arrays, cursor_to_meta(self, optimizer_meta(self.optimizer))

    def load_train_state(self, arrays, meta):
        from ..distributed.checkpoint import (apply_optimizer_meta,
                                              cursor_from_meta)
        sd = self.model.state_dict()
        for n in sd:
            if f"model.{n}" in arrays:
                sd[n]._value = arrays[f"model.{n}"]
        if self._opt_states is None:
            self._opt_states = self._init_opt_states(
                [sd[n]._value for n in self._names])
        for n, st in zip(self._names, self._opt_states):
            for k in st:
                if f"opt.{n}.{k}" in arrays:
                    st[k] = arrays[f"opt.{n}.{k}"]
        apply_optimizer_meta(self.optimizer, meta)
        cursor_from_meta(self, meta)

    def __call__(self, *batch):
        """batch: (*inputs, label) Tensors; returns loss Tensor."""
        model = self.model
        sd = model.state_dict()
        param_vals = [sd[n]._value for n in self._names]
        buf_vals = [sd[n]._value for n in self._buf_names]
        if self._opt_states is None:
            self._opt_states = self._init_opt_states(param_vals)
        if self._compiled is None:
            self._build(batch)
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in batch]
        # inject BEFORE the step counter advances or an RNG key is
        # drawn (same order as the sharded trainers): a caught injected
        # crash must not leave a phantom step behind
        batch_vals = _step_faults(batch_vals, "jit")
        self.optimizer._step_count += 1
        lr = self.optimizer.get_lr()
        key = prandom.next_key()
        args = (param_vals, self._opt_states, buf_vals,
                jnp.asarray(lr, jnp.float32),
                jnp.asarray(self.optimizer._step_count, jnp.int32), key,
                *batch_vals)
        from ..telemetry import compile_cache as _cc, memledger as _ml
        _ml.note_jit(self, "step", self._compiled, args,
                     "jit.TrainStep.step",
                     sig=tuple(b.shape for b in batch_vals))
        fn = _cc.aot_for(self._aot, "step", self._compiled, args,
                         batch_vals, "jit.TrainStep.step")
        from .. import telemetry as _tel
        _tel.counter("train.steps").inc()    # lifetime total, sink or not
        tel_on = _tel.active()
        t0 = time.perf_counter()
        out = fn(*args)
        if getattr(self, "_numerics", False):
            loss, new_params, new_states, new_bufs, nstats = out
        else:
            (loss, new_params, new_states, new_bufs), nstats = out, None
        if tel_on and _tel.config("sync_steps"):
            jax.block_until_ready(loss)
        wall_ms = (time.perf_counter() - t0) * 1e3
        for n, v in zip(self._names, new_params):
            sd[n]._value = v
        for n, v in zip(self._buf_names, new_bufs):
            sd[n]._value = v
        self._opt_states = new_states
        if tel_on:
            _tel.step_event(self, label="jit", kind="step",
                            step=self.optimizer._step_count, k=1,
                            wall_ms=wall_ms, batch_vals=batch_vals)
        if nstats is not None:
            from ..telemetry import numerics as _numerics
            _numerics.record("jit", self.optimizer._step_count, 1,
                             self._num_bundles, nstats)
        return Tensor(loss)


# ---------------------------------------------------------------------------
# save / load (reference: paddle.jit.save / jit.load — TranslatedLayer
# executable artifacts, jit/api.py + fluid/jit/layer.cc)
# ---------------------------------------------------------------------------
def _specs_to_avals(input_spec):
    """InputSpec list → jax avals; -1/None dims become export-time
    symbolic dimensions so one artifact serves any batch size."""
    from jax import export as jexport
    from ..static import InputSpec
    scope = jexport.SymbolicScope()
    avals = []
    sym_names = iter("bcdefghij")
    for spec in input_spec:
        if isinstance(spec, Tensor):
            spec = InputSpec.from_tensor(spec)
        shape = []
        for s in spec.shape:
            if s in (-1, None):
                (dim,) = jexport.symbolic_shape(next(sym_names),
                                                scope=scope)
                shape.append(dim)
            else:
                shape.append(int(s))
        dt = spec.dtype
        dt = dt.name if hasattr(dt, "name") else str(dt)
        avals.append(jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt)))
    return avals


def save(layer, path, input_spec=None, **configs):
    """Serialize an EXECUTABLE artifact: params (`.pdiparams`) + a
    jax.export StableHLO function of (params, *inputs) (`.pdmodel`).
    `jit.load` returns a callable TranslatedLayer; the artifact is also
    what `paddle_tpu.inference.Predictor` serves.

    input_spec: list of InputSpec/Tensors describing the inputs; -1 or
    None dims export symbolically (any size at run time).  Falls back to
    the layer's `forward` StaticFunction input_spec when omitted.
    """
    import pickle
    import os
    from jax import export as jexport

    fn = layer.forward
    if isinstance(fn, StaticFunction):
        input_spec = input_spec or fn._input_spec
        fn = fn._fn
    if input_spec is None:
        raise ValueError(
            "jit.save needs input_spec (or a @to_static layer with one) "
            "to trace the exported function")

    names = list(layer.state_dict().keys())
    state = {k: np.asarray(v.value) for k, v in layer.state_dict().items()}

    def raw(state_vals, *in_vals):
        with _swapped_state(layer, names, list(state_vals)):
            out = fn(*[Tensor(v) for v in in_vals])
        return _leaves_to_values(out)

    param_avals = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in state.values()]
    in_avals = _specs_to_avals(list(input_spec))
    with x64_off():
        exported = jexport.export(jax.jit(raw))(param_avals, *in_avals)
        blob = exported.serialize()

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump(state, f, protocol=4)
    in_names = [getattr(s, "name", None) or f"x{i}"
                for i, s in enumerate(input_spec)]
    meta = {"class": type(layer).__name__,
            "format": "jax.export.v1",
            "param_names": names,
            "input_names": in_names,
            "mlir": blob}
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(meta, f, protocol=4)
    # legacy alias kept for round-1 checkpoints
    with open(path + ".pdparams", "wb") as f:
        pickle.dump(state, f, protocol=4)


class TranslatedLayer:
    """Executable loaded artifact (reference: TranslatedLayer /
    fluid/jit Layer): callable, with state_dict access."""

    def __init__(self, state, exported=None, param_names=None,
                 class_name="", input_names=None):
        self._state = state
        self._exported = exported
        self._param_names = param_names or list(state)
        self._class_name = class_name
        self.input_names = input_names or []

    def state_dict(self):
        return self._state

    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        if self._exported is None:
            raise RuntimeError(
                "artifact has no compiled function (params-only "
                "checkpoint); re-save with paddle.jit.save(..., "
                "input_spec=...)")
        in_vals = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                   for a in args]
        state_vals = [self._state[n]._value for n in self._param_names]
        with x64_off():
            out = self._exported.call(state_vals, *in_vals)
        return jax.tree_util.tree_map(
            lambda x: Tensor(x) if isinstance(x, jax.Array) else x, out)

    def eval(self):
        return self

    def train(self):
        return self


def load(path, **configs):
    import pickle
    import os
    from jax import export as jexport
    exported, param_names, class_name, input_names = None, None, "", None
    if os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            meta = pickle.load(f)
        if isinstance(meta, dict) and meta.get("mlir"):
            exported = jexport.deserialize(meta["mlir"])
            param_names = meta.get("param_names")
            class_name = meta.get("class", "")
            input_names = meta.get("input_names")
    params_path = (path + ".pdiparams"
                   if os.path.exists(path + ".pdiparams")
                   else path + ".pdparams")
    with open(params_path, "rb") as f:
        state = pickle.load(f)
    return TranslatedLayer({k: Tensor(jnp.asarray(v))
                            for k, v in state.items()},
                           exported=exported, param_names=param_names,
                           class_name=class_name, input_names=input_names)
