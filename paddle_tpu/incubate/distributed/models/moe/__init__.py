"""Mixture-of-Experts with expert parallelism.

Reference: `python/paddle/incubate/distributed/models/moe/moe_layer.py:263`
(MoELayer), gates `moe/gate/` (naive/switch/gshard), alltoall dispatch
`python/paddle/distributed/utils/moe_utils.py:20` (global_scatter/
global_gather), SPMD rule `paddle/phi/infermeta/spmd_rules/
moe_gate_dispatch.cc`.

TPU-native redesign (the GShard pattern): dispatch is not a hand-written
alltoall — it's a pair of einsums over a [tokens, experts, capacity]
one-hot dispatch/combine tensor.  With tokens sharded on the data axis and
the stacked expert weights sharded on the expert dim over the `ep` axis,
GSPMD lowers the dispatch einsum to exactly the reference's all_to_all.
Gates:

  naive   — top-k softmax, no capacity, no aux loss
  switch  — top-1, capacity-bounded, load-balance aux loss (Fedus et al.)
  gshard  — top-2, capacity-bounded, aux loss (Lepikhin et al.)
  sigmoid — top-k of fp32 sigmoid scores plus a choice-only bias, weights
            renormalised over the chosen and scaled (DeepSeek-V3's
            auxiliary-loss-free router), no capacity, no aux loss

Tokens over capacity are dropped (combine weight 0 → residual passthrough
is the caller's choice, as in the reference).  The gates WITHOUT a capacity
(naive, sigmoid) drop nothing: their assignments are sorted by expert and
the experts run as one grouped product over the sorted rows
(`dropless_experts`, `ops.grouped_matmul`), so the work grows with the
assignments, not with experts x tokens; and the sorted buffer is as long
as the assignments a layer HOLDS for valid lanes, rounded up to a rung of
a short ladder (`sorted_lengths`), not as long as all tokens x k.

A layer may hold only ITS SHARE of the experts (`experts_held=(first,
count)` of a router `router_width` wide): it routes over all of them,
normalises over all the chosen, and computes the part of the result its own
experts give.  What the absent experts would add is the other chips' part;
on one chip the layer runs without the exchange.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..... import ops
from .....nn import Layer
from .....nn import functional as F
from .....nn import initializer as I
from .....framework.dispatch import run, to_tensor_args
from .....framework.tensor import Tensor

__all__ = ["MoELayer", "NaiveGate", "SwitchGate", "GShardGate",
           "SigmoidGate", "ExpertMLP", "StepCounters", "dropless_experts",
           "COUNTER_NAMES"]

# what a dropless layer counts a step, on the device (StepCounters): every
# name a SUM over layers and steps but one that ends in `_max`, a maximum.
# `moe_rows_sorted` is the length of the sorted buffer a layer took (the
# rung of `sorted_lengths`); over `moe_assignments_held` it says how tight
# the buffer sits around the rows that are used.  `moe_rows_walked` is the
# rows the layer's first grouped product walked (ops.grouped_rows_walked:
# the kernel's row tiles that hold rows, or the whole buffer).
COUNTER_NAMES = ("moe_assignments", "moe_assignments_held",
                 "moe_expert_steps_hit", "moe_rows_sorted",
                 "moe_rows_walked", "moe_tokens_per_expert_max")
_N_SUMS = sum(not name.endswith("_max") for name in COUNTER_NAMES)

# A rung is left out where it is shorter than this: below it the row-sized
# ops cost less than the conditional around them (chip_smoke.py, phase
# `moe`: the table in CHANGES.md, PR 35).
MIN_RUNG_ROWS = 1024
# The rows go back to their tokens by a scatter-add of R rows where R is at
# most this fraction of S*k, by the parent's gather of S*k rows above it
# (the same table: the scatter-add costs 0.33 ms a 1,024 rows of 6,144, the
# gather, select and sum out of a buffer of R rows 0.7 ms whatever R).
GATHER_BACK_OVER = 8


def sorted_lengths(n):
    """The lengths the sorted buffer of `n` = S*k assignments may take,
    ascending, from `n` alone: a sixteenth, a quarter, all of them.  The
    last is always `n`, so every routing is served whole."""
    return tuple(n // q for q in (16, 4)
                 if n % q == 0 and n // q >= MIN_RUNG_ROWS) + (n,)


class StepCounters:
    """Routing counts of one step program, made by whoever traces the
    step and handed down to the expert layers, which add to it while
    they are traced: `valid` [tokens] marks the lanes the step keeps
    (the others are routed nowhere and cost no expert work).  `vector()`
    is int32 [len(COUNTER_NAMES)]: assignments of valid tokens,
    those that chose an expert held here, (layer, held expert) pairs
    with at least one token, the rows of the sorted buffers the layers
    took, the rows their grouped products walked, and the largest single
    (layer, expert) load — the sums add over layers and steps, the last is
    a maximum."""

    def __init__(self, valid=None):
        self.valid = None if valid is None else valid.reshape(-1)
        self._sums = jnp.zeros((_N_SUMS,), jnp.int32)
        self._max = jnp.zeros((), jnp.int32)

    def add(self, n_valid, top_k, sizes, rows_sorted, rows_walked):
        """sizes [count]: valid tokens each held expert got; rows_sorted:
        the length of the buffer the layer sorted them into; rows_walked:
        the rows of it the grouped product walked."""
        self._sums = self._sums + jnp.stack(
            [n_valid * top_k, jnp.sum(sizes),
             jnp.sum((sizes > 0).astype(jnp.int32)),
             rows_sorted, rows_walked]).astype(jnp.int32)
        self._max = jnp.maximum(self._max, jnp.max(sizes))

    def vector(self):
        return jnp.concatenate([self._sums, self._max[None]])

    @staticmethod
    def merge(steps):
        """[steps, len(COUNTER_NAMES)] of a scan -> [len(COUNTER_NAMES)]."""
        return jnp.concatenate([jnp.sum(steps[:, :_N_SUMS], axis=0),
                                jnp.max(steps[:, _N_SUMS:], axis=0)])


def dropless_experts(tokens, topi, topw, w1, w2, act, first=0, valid=None,
                     b1=None, b2=None, counters=None):
    """sum_j topw[s, j] * expert_{topi[s, j]}(tokens[s]) over the chosen
    experts HELD here, without a capacity: tokens [S, d]; topi [S, k]
    ids over the router's whole width; topw [S, k] fp32; w1
    [count, d, *], w2 [count, *, d] the held experts first..first+count
    (optional biases [count, 1, *]).  The S*k assignments are sorted by
    held expert (absent experts' and invalid tokens' last); the first R
    of them, R the shortest of `sorted_lengths(S*k)` that holds every
    assignment HELD (chosen on the device, `jax.lax.switch`), are
    gathered in that order, both products run grouped
    (`ops.grouped_matmul`: on a TPU a Pallas kernel that streams each hit
    expert's weights once and walks only the row tiles that hold rows),
    and each token sums its rows back in fp32.
    The longest rung is all S*k, so nothing is ever dropped.  Returns
    [S, d] fp32."""
    return _sorted_experts(tokens, topi, topw, w1, w2, act, first, valid,
                           b1, b2, counters, sorted_lengths(topi.size))


def _sorted_experts(tokens, topi, topw, w1, w2, act, first, valid, b1, b2,
                    counters, lengths):
    """`dropless_experts` over the given rungs: `(S*k,)` where the caller
    knows at trace time that every assignment is held (no conditional)."""
    S, k = topi.shape
    count = w1.shape[0]
    local = topi.astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    with jax.named_scope("moe.dispatch"):
        key = jnp.where(held, local, count).reshape(-1)
        order = jnp.argsort(key, stable=True)
        # int32 whatever jax_enable_x64 says: the grouped product's
        # TPU lowering takes no 64-bit group sizes
        sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0,
                        promote_integers=False)
    if len(lengths) == 1:
        rung = 0
        y = _rung(S * k, act, tokens, order, key, sizes, held, topw, w1, w2,
                  b1, b2)
    else:
        n_held = jnp.sum(sizes, promote_integers=False)
        rung = sum((n_held > r).astype(jnp.int32) for r in lengths[:-1])
        y = jax.lax.switch(
            rung, [functools.partial(_rung, r, act) for r in lengths],
            tokens, order, key, sizes, held, topw, w1, w2, b1, b2)
    if counters is not None:
        n_valid = S if valid is None else jnp.sum(valid.astype(jnp.int32))
        walked = [ops.grouped_rows_walked(sizes, (r, tokens.shape[1]),
                                          w1.shape, tokens.dtype)
                  for r in lengths]
        counters.add(n_valid, k, sizes,
                     jnp.asarray(lengths, jnp.int32)[rung],
                     jnp.stack(walked)[rung])
    return y


def _rung(R, act, tokens, order, key, sizes, held, topw, w1, w2, b1, b2):
    """The layer's result [S, d] fp32 from the first R sorted assignments:
    every held one lies among them (the caller's choice of R)."""
    S, k = topw.shape
    with jax.named_scope("moe.dispatch"):
        taken = order[:R]
        rows = jnp.take(tokens, taken // k, axis=0)              # [R, d]
    with jax.named_scope("moe.experts"):
        expert = jnp.take(key, taken)      # of each sorted row
        h = ops.grouped_matmul(rows, w1, sizes)
        if b1 is not None:
            h = h + jnp.take(b1[:, 0], expert, axis=0, mode="clip")
        out = ops.grouped_matmul(_expert_act(h, act).astype(rows.dtype), w2,
                                 sizes)
        if b2 is not None:
            out = out + jnp.take(b2[:, 0], expert, axis=0, mode="clip")
    with jax.named_scope("moe.combine"):
        if R * GATHER_BACK_OVER > S * k:
            # each token gathers its k rows: S*k rows moved whatever R is
            back = jnp.zeros((S * k,), jnp.int32).at[order].set(
                jnp.arange(S * k, dtype=jnp.int32))
            mine = jnp.take(out, back, axis=0).reshape(S, k, -1)
            # rows past the last group are whatever the kernel left there
            mine = jnp.where(held[..., None], mine.astype(jnp.float32), 0.0)
            return jnp.sum(mine * topw[..., None].astype(jnp.float32),
                           axis=1)
        # each row is added to its token's, weighted, in fp32: R rows moved
        live = jnp.take(held.reshape(-1), taken)
        weight = jnp.take(topw.reshape(-1).astype(jnp.float32), taken)
        mine = jnp.where(live[:, None],
                         out.astype(jnp.float32) * weight[:, None], 0.0)
        return jnp.zeros((S, out.shape[-1]), jnp.float32).at[
            taken // k].add(mine)


def _topk_dispatch(gates, k, capacity):
    """Build dispatch/combine [S, E, C] and the load-balance aux loss.

    gates: [S, E] softmax probabilities.  Positions are assigned in token
    order per expert (cumsum), choice j's positions offset by choice
    <j's counts — the GShard assignment."""
    S, E = gates.shape
    topv, topi = jax.lax.top_k(gates, k)
    denom = jnp.sum(topv, axis=-1, keepdims=True)
    normv = topv / jnp.maximum(denom, 1e-9)
    counts = jnp.zeros((E,), jnp.float32)
    dispatch = jnp.zeros((S, E, capacity), gates.dtype)
    combine = jnp.zeros((S, E, capacity), gates.dtype)
    first_mask = None
    for j in range(k):
        oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.float32)     # [S,E]
        if first_mask is None:
            first_mask = oh
        pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]        # [S,E]
        within = (pos < capacity) & (oh > 0)
        sel = oh * within                                          # [S,E]
        tok_pos = jnp.sum(pos * sel, axis=-1)                      # [S]
        pc = jax.nn.one_hot(tok_pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)                     # [S,C]
        d_j = sel[:, :, None] * pc[:, None, :]
        dispatch = dispatch + d_j.astype(dispatch.dtype)
        combine = combine + (normv[:, j, None, None]
                             * d_j).astype(combine.dtype)
        counts = counts + jnp.sum(sel, axis=0)
    # load balancing: E * sum(mean_prob * mean_first_choice_fraction)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(first_mask, axis=0)
    aux = E * jnp.sum(me * ce)
    return dispatch, combine, aux


class _GateBase(Layer):
    """Learned router. Reference: moe/gate/base_gate.py + subclasses."""

    top_k = 1
    use_capacity = True
    use_aux = True

    def __init__(self, d_model, num_experts, capacity_factor=None,
                 dtype=None):
        super().__init__(dtype=dtype)
        self.d_model = d_model
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.weight = self.create_parameter(
            shape=[d_model, num_experts],
            default_initializer=I.XavierUniform())

    def capacity(self, num_tokens):
        if not self.use_capacity:
            return num_tokens
        cf = self.capacity_factor if self.capacity_factor is not None \
            else (1.25 if self.top_k == 1 else 2.0)
        return max(self.top_k,
                   int(math.ceil(cf * num_tokens / self.num_experts)))



class NaiveGate(_GateBase):
    """Reference: moe/gate/naive_gate.py — top-k, no capacity bound."""
    top_k = 2
    use_capacity = False
    use_aux = False

    def __init__(self, d_model, num_experts, top_k=2, dtype=None, **kw):
        super().__init__(d_model, num_experts, dtype=dtype)
        self.top_k = top_k


class SwitchGate(_GateBase):
    """Reference: moe/gate/switch_gate.py — top-1 + capacity + aux."""
    top_k = 1


class GShardGate(_GateBase):
    """Reference: moe/gate/gshard_gate.py — top-2 + capacity + aux."""
    top_k = 2


class SigmoidGate(_GateBase):
    """DeepSeek-V3's router: s = sigmoid(x W) in fp32 over the whole
    width; the top_k largest of s + bias are chosen (`bias` is a buffer
    the training balances, used for the CHOICE only); their weights are
    scaling * s / sum of the chosen s.  One routing group, no capacity,
    no auxiliary loss."""
    top_k = 8
    use_capacity = False
    use_aux = False

    def __init__(self, d_model, num_experts, top_k=8, scaling=1.0,
                 bias=True, dtype=None, **kw):
        super().__init__(d_model, num_experts, dtype=dtype)
        self.top_k = top_k
        self.scaling = float(scaling)
        self.bias = self.register_buffer(
            "bias", Tensor(jnp.zeros((num_experts,), jnp.float32))) \
            if bias else None

    def route(self, tokens, weight, bias):
        """(topi [S, k], topw [S, k] fp32, scores [S, E] fp32)."""
        s = jax.nn.sigmoid(jnp.matmul(
            tokens.astype(jnp.float32), weight.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        pick = s if bias is None else s + bias.astype(jnp.float32)
        _, topi = jax.lax.top_k(pick, self.top_k)
        chosen = jnp.take_along_axis(s, topi, axis=-1)
        topw = self.scaling * chosen / jnp.sum(chosen, -1, keepdims=True)
        return topi, topw, s


class ExpertMLP(Layer):
    """One expert: Linear → activation → Linear (the reference's
    ExpertLayer shape)."""

    def __init__(self, d_model, d_hidden, activation=F.gelu):
        super().__init__()
        self.fc1 = __import__("paddle_tpu").nn.Linear(d_model, d_hidden)
        self.fc2 = __import__("paddle_tpu").nn.Linear(d_hidden, d_model)
        self.act = activation

    def forward(self, x):
        return self.fc2(self.act(self.fc1(x)))


class MoELayer(Layer):
    """Reference: moe_layer.py:263.

    Two construction styles:
      MoELayer(d_model, d_hidden, num_experts=E, gate="gshard") —
        TPU-native stacked expert weights [E, d, h]/[E, h, d], expert dim
        sharded over `ep_axis` when a hybrid mesh is active; expert
        compute is ONE batched einsum (MXU-friendly), dispatch/combine
        einsums carry the all_to_all.
      MoELayer(gate=<Layer>, experts=[Layer...]) — reference style with
        arbitrary expert networks (looped; correct but slower).

    The load-balance aux loss of the last forward is on `self.l_aux`
    (reference keeps it the same way).

    A share of an expert-parallel layer: `num_experts` experts are HELD,
    `experts_held=(first, count)` says which of the router's
    `router_width` they are (stacked style only; count must equal
    num_experts).  gate="sigmoid" takes `routed_scaling`, `router_bias`
    and `shared_hidden` (one always-on swiglu expert that wide, computed
    whole on every chip) and has no expert biases; `expert_bias=False`
    leaves them out under gate="naive" too (the capacity gates' einsum
    path always has them).  `dtype`: what the leaves are held in (the
    default float32, as every Layer).
    """

    def __init__(self, d_model=None, d_hidden=None, num_experts=None,
                 gate="gshard", experts: Optional[List[Layer]] = None,
                 top_k=None, capacity_factor=None, ep_axis="dp",
                 moe_group=None, recompute_interval=0,
                 activation="gelu", experts_held=None, router_width=None,
                 routed_scaling=1.0, router_bias=False, shared_hidden=0,
                 expert_bias=True, dtype=None, **kw):
        super().__init__(dtype=dtype)
        if not expert_bias and gate not in ("naive", "sigmoid"):
            raise ValueError("experts without biases need a dropless "
                             "gate (naive|sigmoid) and stacked experts")
        held_n = num_experts if num_experts else len(experts or ())
        self.first_expert, count = experts_held or (0, held_n)
        width = router_width or held_n
        if count != held_n or self.first_expert < 0 \
                or self.first_expert + count > width:
            raise ValueError(
                f"experts_held {experts_held} does not name {held_n} "
                f"experts of a router {width} wide")
        if width != held_n and (experts is not None or not isinstance(
                gate, str) or gate not in ("naive", "sigmoid")):
            raise ValueError("a share of the experts needs a dropless "
                             "gate (naive|sigmoid) and stacked experts")
        # "swiglu": llama/Mixtral-style experts — w1 holds gate+up
        # halves ([E, d, 2*dh]); "gelu": the reference ExpertLayer MLP
        self.activation = activation
        if isinstance(gate, str):
            if experts is not None and d_model is None:
                d_model = experts[0].fc1.weight.shape[0]
            cls = {"naive": NaiveGate, "switch": SwitchGate,
                   "gshard": GShardGate, "sigmoid": SigmoidGate}[gate]
            kwargs = {}
            if top_k is not None and cls in (NaiveGate, SigmoidGate):
                kwargs["top_k"] = top_k
            if cls is NaiveGate:
                kwargs.update(dtype=dtype)
            if cls is SigmoidGate:
                kwargs.update(scaling=routed_scaling, bias=router_bias,
                              dtype=dtype)
            self.gate = cls(d_model, width,
                            **({"capacity_factor": capacity_factor}
                               | kwargs))
            if top_k is not None:
                self.gate.top_k = top_k
        else:
            self.gate = gate
        self.ep_axis = ep_axis
        self.experts_list = None
        if experts is not None:
            from .....nn import LayerList
            self.experts = LayerList(experts)
            self.experts_list = list(experts)
            self.num_experts = len(experts)
        else:
            assert d_model and d_hidden and num_experts
            self.num_experts = num_experts
            w1_h = 2 * d_hidden if activation == "swiglu" else d_hidden
            self.w1 = self.create_parameter(
                shape=[num_experts, d_model, w1_h],
                default_initializer=I.XavierUniform())
            self.w2 = self.create_parameter(
                shape=[num_experts, d_hidden, d_model],
                default_initializer=I.XavierUniform())
            self.b1 = self.b2 = None
            if gate != "sigmoid" and expert_bias:
                self.b1 = self.create_parameter(
                    shape=[num_experts, 1, w1_h], is_bias=True)
                self.b2 = self.create_parameter(
                    shape=[num_experts, 1, d_model], is_bias=True)
            self.shared_w1 = self.shared_w2 = None
            if shared_hidden:
                self.shared_w1 = self.create_parameter(
                    shape=[d_model, 2 * shared_hidden],
                    default_initializer=I.XavierUniform())
                self.shared_w2 = self.create_parameter(
                    shape=[shared_hidden, d_model],
                    default_initializer=I.XavierUniform())
            self._shard_experts()
        self.l_aux = None

    def _shard_experts(self):
        from .....distributed import topology as topo
        hcg = topo.get_hybrid_communicate_group()
        mesh = hcg.mesh if hcg is not None else None
        if mesh is None or self.ep_axis not in mesh.axis_names \
                or mesh.shape[self.ep_axis] == 1 \
                or self.num_experts % mesh.shape[self.ep_axis]:
            return
        for w, nd in ((self.w1, 3), (self.b1, 3), (self.w2, 3),
                      (self.b2, 3)):
            if w is None:
                continue
            spec = [self.ep_axis] + [None] * (nd - 1)
            try:
                w._value = jax.device_put(
                    w._value, NamedSharding(mesh, P(*spec)))
            except Exception:
                pass

    def _dropless(self, xv, vals, valid=None, counters=None):
        """The gates without a capacity, on raw values: route (fp32),
        sorted dispatch, grouped experts, the shared expert beside
        them.  vals: {name: value} of this layer's leaves."""
        gate, act = self.gate, self.activation
        cd, shape = xv.dtype, xv.shape
        tokens = xv.reshape(-1, shape[-1])
        with jax.named_scope("moe.route"):
            if isinstance(gate, SigmoidGate):
                topi, topw, _ = gate.route(tokens, vals["gate"],
                                           vals.get("bias"))
            else:
                # HIGHEST, as SigmoidGate.route: a TPU's default fp32
                # product is one bf16 pass, and the choice of experts
                # hangs on near-ties of these scores
                probs = jax.nn.softmax(jnp.matmul(
                    tokens.astype(jnp.float32),
                    vals["gate"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST), axis=-1)
                topv, topi = jax.lax.top_k(probs, gate.top_k)
                topw = topv / jnp.maximum(
                    jnp.sum(topv, -1, keepdims=True), 1e-9)

        def cast(name):
            return None if vals.get(name) is None else vals[name].astype(cd)
        # what is known while tracing is decided while tracing: with no
        # lane invalid and every expert of the router held here, every
        # assignment is held and the buffer is all S*k rows
        n = topi.size
        every_held = valid is None and self.num_experts == gate.num_experts
        y = _sorted_experts(tokens, topi, topw, cast("w1"), cast("w2"),
                            act, self.first_expert, valid, cast("b1"),
                            cast("b2"), counters,
                            (n,) if every_held else sorted_lengths(n))
        if vals.get("shared_w1") is not None:
            with jax.named_scope("moe.shared"):
                h = _expert_act(tokens @ cast("shared_w1"), "swiglu")
                y = y + (h @ cast("shared_w2")).astype(jnp.float32)
        return y.astype(cd).reshape(shape)

    def _dropless_leaves(self):
        leaves = {"gate": self.gate.weight, "w1": self.w1, "w2": self.w2,
                  "b1": self.b1, "b2": self.b2,
                  "bias": getattr(self.gate, "bias", None),
                  "shared_w1": self.shared_w1, "shared_w2": self.shared_w2}
        return {k: v for k, v in leaves.items() if v is not None}

    def forward(self, x, valid=None, counters=None):
        """valid [tokens] bool and counters (a StepCounters): the step
        programs' handles on a dropless layer — invalid lanes are routed
        nowhere, the routing is counted."""
        (x,) = to_tensor_args(x)
        gate = self.gate
        gw = gate.weight
        if self.experts_list is None and not gate.use_capacity:
            leaves = self._dropless_leaves()
            names = list(leaves)

            def fn(xv, *ws):
                y = self._dropless(xv, dict(zip(names, ws)), valid,
                                   counters)
                return y, jnp.zeros((), jnp.float32)
            out, aux = run(fn, x, *leaves.values(), name="moe")
            self.l_aux = aux
            return out
        if self.experts_list is None:
            act = self.activation
            params = [gw, self.w1, self.b1, self.w2, self.b2]

            def fn(xv, gwv, w1, b1, w2, b2):
                # storage dtype may be fp32 masters; compute in the
                # activation dtype like the dense MLP path (a missing
                # cast silently promotes the residual stream to fp32)
                cd = xv.dtype
                w1, b1 = w1.astype(cd), b1.astype(cd)
                w2, b2 = w2.astype(cd), b2.astype(cd)
                shape = xv.shape
                tokens = xv.reshape(-1, shape[-1])
                logits = tokens.astype(jnp.float32) @ gwv.astype(
                    jnp.float32)
                gates = jax.nn.softmax(logits, axis=-1)
                cap = gate.capacity(tokens.shape[0])
                dispatch, combine, aux = _topk_dispatch(
                    gates, gate.top_k, cap)
                if not gate.use_aux:
                    aux = jnp.zeros((), jnp.float32)
                expert_in = jnp.einsum("sec,sm->ecm",
                                       dispatch.astype(xv.dtype), tokens)
                h = _expert_act(
                    jnp.einsum("ecm,emh->ech", expert_in, w1) + b1,
                    act)
                expert_out = jnp.einsum("ech,ehm->ecm", h, w2) + b2
                y = jnp.einsum("sec,ecm->sm",
                               combine.astype(xv.dtype), expert_out)
                return y.reshape(shape), aux

            out, aux = run(fn, x, gw, self.w1, self.b1, self.w2, self.b2,
                           name="moe")
            self.l_aux = aux
            return out

        # reference-style expert list: loop experts (correct, not fast)
        shape = x.shape
        d = shape[-1]
        from .....tensor.manipulation import reshape
        tokens = reshape(x, [-1, d])

        def route_fn(tv, gwv):
            logits = tv.astype(jnp.float32) @ gwv.astype(jnp.float32)
            gates = jax.nn.softmax(logits, axis=-1)
            cap = gate.capacity(tv.shape[0])
            return _topk_dispatch(gates, gate.top_k, cap)

        dispatch, combine, aux = run(route_fn, tokens, gw,
                                     name="moe_route")
        self.l_aux = aux
        y = None
        for e, expert in enumerate(self.experts_list):
            de = dispatch[:, e, :]      # [S, C]
            ce = combine[:, e, :]
            xin = paddle_matmul_t(de, tokens)   # [C, d]
            xout = expert(xin)
            contrib = paddle_matmul(ce, xout)   # [S, d]
            y = contrib if y is None else y + contrib
        return reshape(y, list(shape))


def _expert_act(h, act):
    if act == "swiglu":
        half = h.shape[-1] // 2
        return jax.nn.silu(h[..., :half]) * h[..., half:]
    return jax.nn.gelu(h)


def paddle_matmul(a, b):
    from .....tensor.math import matmul
    return matmul(a, b)


def paddle_matmul_t(a, b):
    from .....tensor.math import matmul
    return matmul(a, b, transpose_x=True)
