"""ONNX export (reference: `python/paddle/onnx/export.py` —
`paddle.onnx.export(layer, path, input_spec)` via paddle2onnx).

TPU-native two-tier design:

* The NATIVE interchange format for XLA-compiled models remains
  serialized StableHLO (`jit.save` — versioned, loadable by any
  OpenXLA consumer); `export(..., format="stablehlo")` produces it.
* `export(..., format="onnx")` emits a REAL ONNX ModelProto for
  external ONNX consumers (the reference's capability): the layer is
  traced to a jaxpr and each primitive is mapped to an ONNX op.  The
  protobuf is written with a hand-rolled wire-format encoder
  (`_Proto`) — the environment ships no onnx package, and the
  format's wire layout is stable (proto3: varint tags,
  length-delimited submessages).

The supported primitive set covers Linear/MLP/conv-free inference
graphs (dot_general, elementwise, activations, reshape/transpose/
broadcast, reductions, softmax composition); an unsupported primitive
raises with its name rather than emitting a wrong graph.
"""
from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["export", "load", "export_onnx"]

ONNX_IR_VERSION = 8
ONNX_OPSET = 17


# ---------------------------------------------------------------------------
# minimal protobuf wire-format writer
# ---------------------------------------------------------------------------
class _Proto:
    """Append-only proto3 message builder (wire format: tag =
    field_number << 3 | wire_type; 0 = varint, 2 = length-delimited)."""

    def __init__(self):
        self._buf = bytearray()

    @staticmethod
    def _varint(n: int) -> bytes:
        out = bytearray()
        n &= (1 << 64) - 1
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def varint(self, field: int, value: int):
        self._buf += self._varint(field << 3 | 0)
        self._buf += self._varint(value)
        return self

    def bytes_(self, field: int, raw: bytes):
        self._buf += self._varint(field << 3 | 2)
        self._buf += self._varint(len(raw))
        self._buf += raw
        return self

    def string(self, field: int, s: str):
        return self.bytes_(field, s.encode())

    def message(self, field: int, sub: "_Proto"):
        return self.bytes_(field, bytes(sub._buf))

    def __bytes__(self):
        return bytes(self._buf)


# ONNX TensorProto.DataType
_DT = {"float32": 1, "uint8": 2, "int8": 3, "int32": 6, "int64": 7,
       "bool": 9, "float64": 11, "bfloat16": 16}


def _tensor_proto(name, arr):
    arr = np.asarray(arr)
    dt = _DT.get(str(arr.dtype))  # bfloat16 → 16 (true ONNX BFLOAT16)
    if dt is None:
        raise NotImplementedError(
            f"onnx export: dtype {arr.dtype} has no mapping — "
            "refusing to emit a numerically different graph")
    t = _Proto()
    for d in arr.shape:
        t.varint(1, int(d))            # dims
    t.varint(2, dt)                    # data_type
    t.string(8, name)                  # name
    t.bytes_(9, arr.tobytes())         # raw_data
    return t


def _value_info(name, shape, dtype="float32"):
    dim_msgs = _Proto()
    tt = _Proto()
    tt.varint(1, _DT.get(str(dtype), 1))            # elem_type
    shp = _Proto()
    for d in shape:
        dim = _Proto()
        dim.varint(1, int(d))                       # dim_value
        shp.message(1, dim)
    tt.message(2, shp)                              # shape
    ty = _Proto()
    ty.message(1, tt)                               # tensor_type
    vi = _Proto()
    vi.string(1, name)
    vi.message(2, ty)
    return vi


def _node(op_type, inputs, outputs, **attrs):
    n = _Proto()
    for i in inputs:
        n.string(1, i)
    for o in outputs:
        n.string(2, o)
    n.string(4, op_type)
    for k, v in attrs.items():
        a = _Proto()
        a.string(1, k)
        if isinstance(v, int):
            a.varint(3, v)      # i (AttributeProto field 3, int64)
            a.varint(20, 2)     # type INT
        elif isinstance(v, (list, tuple)):
            for x in v:
                a.varint(8, int(x))   # ints (packed not required)
            a.varint(20, 7)     # type INTS
        elif isinstance(v, np.ndarray):
            a.message(5, _tensor_proto(k, v))  # t
            a.varint(20, 4)     # type TENSOR
        elif isinstance(v, bytes):
            a.bytes_(4, v)   # s (AttributeProto.STRING)
            a.varint(20, 3)      # type STRING
        else:
            raise TypeError(f"attr {k}: {type(v)}")
        n.message(5, a)
    return n


# ---------------------------------------------------------------------------
# jaxpr → ONNX graph
# ---------------------------------------------------------------------------
def _convert_jaxpr(jaxpr, consts, in_names, prefix="", opset=None):
    """Returns (nodes, initializers, env) mapping jaxpr vars to names."""
    nodes, inits = [], []
    env = {}
    ctr = [0]

    def fresh(base):
        ctr[0] += 1
        return f"{prefix}{base}_{ctr[0]}"

    def name_of(atom):
        from jax._src.core import Literal
        if isinstance(atom, Literal):
            nm = fresh("const")
            inits.append(_tensor_proto(nm, np.asarray(atom.val)))
            return nm
        return env[atom]

    for var, const in zip(jaxpr.constvars, consts):
        nm = fresh("w")
        inits.append(_tensor_proto(nm, np.asarray(const)))
        env[var] = nm
    for var, nm in zip(jaxpr.invars, in_names):
        env[var] = nm

    simple = {"add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div",
              "max": "Max", "min": "Min", "tanh": "Tanh",
              "logistic": "Sigmoid", "exp": "Exp", "log": "Log",
              "neg": "Neg", "sqrt": "Sqrt", "rsqrt": None,
              "abs": "Abs", "pow": "Pow", "erf": "Erf",
              "floor": "Floor", "ceil": "Ceil", "sign": "Sign",
              "lt": "Less", "le": "LessOrEqual", "gt": "Greater",
              "ge": "GreaterOrEqual", "eq": "Equal", "not": "Not",
              "and": "And", "or": "Or"}

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [name_of(a) for a in eqn.invars]
        outs = [fresh(prim) for _ in eqn.outvars]
        for v, nm in zip(eqn.outvars, outs):
            env[v] = nm
        p = eqn.params
        if prim in ("jit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "remat", "checkpoint"):
            inner = p.get("jaxpr") or p.get("call_jaxpr")
            closed = inner if hasattr(inner, "jaxpr") else None
            ij = closed.jaxpr if closed else inner
            iconsts = closed.consts if closed else []
            sub_nodes, sub_inits, sub_env = _convert_jaxpr(
                ij, iconsts, ins, prefix=fresh("sub") + "/",
                opset=opset)
            nodes += sub_nodes
            inits += sub_inits
            for v, ov in zip(eqn.outvars, ij.outvars):
                env[v] = sub_env[ov] if not hasattr(ov, "val") \
                    else name_of(ov)
            continue
        if prim in simple and simple[prim]:
            nodes.append(_node(simple[prim], ins, outs))
        elif prim == "rsqrt":
            mid = fresh("sqrt")
            nodes.append(_node("Sqrt", ins, [mid]))
            nodes.append(_node("Reciprocal", [mid], outs))
        elif prim == "integer_pow":
            y = np.asarray(float(p["y"]), np.float32)
            cn = fresh("pow_y")
            inits.append(_tensor_proto(cn, y))
            nodes.append(_node("Pow", [ins[0], cn], outs))
        elif prim == "dot_general":
            ((lc, rc), (lb, rb)) = p["dimension_numbers"]
            lhs_aval, rhs_aval = (a.aval for a in eqn.invars)
            if lb or rb or len(lc) != 1 or len(rc) != 1 \
                    or lhs_aval.ndim > 2 or rhs_aval.ndim > 2:
                # >2-D operands would hit MatMul's implicit batch
                # broadcasting, which reorders dims differently from
                # dot_general — refuse rather than emit a wrong graph
                raise NotImplementedError(
                    "onnx export: batched/multi-contract/>2-D "
                    "dot_general")
            a, b = ins
            # MatMul contracts lhs last dim with rhs second-to-last
            if lc[0] != lhs_aval.ndim - 1:
                perm = [i for i in range(lhs_aval.ndim) if i != lc[0]] \
                    + [lc[0]]
                t = fresh("tA")
                nodes.append(_node("Transpose", [a], [t], perm=perm))
                a = t
            if rc[0] != max(rhs_aval.ndim - 2, 0):
                perm = list(range(rhs_aval.ndim))
                perm.remove(rc[0])
                perm.insert(max(rhs_aval.ndim - 2, 0), rc[0])
                t = fresh("tB")
                nodes.append(_node("Transpose", [b], [t], perm=perm))
                b = t
            nodes.append(_node("MatMul", [a, b], outs))
        elif prim == "reshape":
            shp = np.asarray(eqn.outvars[0].aval.shape, np.int64)
            cn = fresh("shape")
            inits.append(_tensor_proto(cn, shp))
            nodes.append(_node("Reshape", [ins[0], cn], outs))
        elif prim == "transpose":
            nodes.append(_node("Transpose", ins, outs,
                               perm=list(p["permutation"])))
        elif prim == "broadcast_in_dim":
            shp = np.asarray(p["shape"], np.int64)
            in_aval = eqn.invars[0].aval
            src = ins[0]
            # insert length-1 dims so numpy-style broadcast applies
            if in_aval.ndim != len(p["shape"]):
                mid_shape = [1] * len(p["shape"])
                for ax, d in zip(p["broadcast_dimensions"],
                                 in_aval.shape):
                    mid_shape[ax] = int(d)
                cn = fresh("bshape")
                inits.append(_tensor_proto(
                    cn, np.asarray(mid_shape, np.int64)))
                mid = fresh("rshp")
                nodes.append(_node("Reshape", [src, cn], [mid]))
                src = mid
            cn = fresh("eshape")
            inits.append(_tensor_proto(cn, shp))
            nodes.append(_node("Expand", [src, cn], outs))
        elif prim == "convert_element_type":
            dt_name = str(np.dtype(p["new_dtype"]))
            to = _DT.get(dt_name)   # bfloat16 hits the real enum (16)
            if to is None:
                raise NotImplementedError(
                    f"onnx export: Cast to unmapped dtype {dt_name}")
            nodes.append(_node("Cast", ins, outs, to=to))
        elif prim == "reduce_sum":
            # ReduceSum takes axes as an INPUT from opset 13
            axes = np.asarray(p["axes"], np.int64)
            cn = fresh("axes")
            inits.append(_tensor_proto(cn, axes))
            nodes.append(_node("ReduceSum", [ins[0], cn], outs,
                               keepdims=0))
        elif prim in ("reduce_max", "reduce_min"):
            # axes moved from attribute to INPUT at opset 18 for these
            op = {"reduce_max": "ReduceMax",
                  "reduce_min": "ReduceMin"}[prim]
            if (opset or ONNX_OPSET) >= 18:
                cn = fresh("axes")
                inits.append(_tensor_proto(
                    cn, np.asarray(p["axes"], np.int64)))
                nodes.append(_node(op, [ins[0], cn], outs, keepdims=0))
            else:
                nodes.append(_node(op, [ins[0]], outs,
                                   axes=[int(a) for a in p["axes"]],
                                   keepdims=0))
        elif prim == "stop_gradient":
            nodes.append(_node("Identity", ins, outs))
        elif prim == "select_n" and len(ins) == 3:
            # select_n(pred, a, b) == Where(pred, b, a)
            nodes.append(_node("Where", [ins[0], ins[2], ins[1]], outs))
        elif prim == "conv_general_dilated":
            dn = p["dimension_numbers"]
            nd = len(p["window_strides"])
            canon = tuple(range(nd + 2))
            if dn.lhs_spec != canon or dn.rhs_spec != canon \
                    or dn.out_spec != canon:
                raise NotImplementedError(
                    "onnx export: conv with non-NCHW/OIHW layout")
            if any(d != 1 for d in p["lhs_dilation"]):
                raise NotImplementedError(
                    "onnx export: transposed conv (lhs_dilation>1) — "
                    "ONNX ConvTranspose flips the weight layout; use "
                    "format='stablehlo'")
            if p.get("batch_group_count", 1) != 1:
                raise NotImplementedError(
                    "onnx export: batch_group_count > 1")
            pads = [int(lo) for lo, _ in p["padding"]] \
                + [int(hi) for _, hi in p["padding"]]
            nodes.append(_node(
                "Conv", ins, outs,
                strides=[int(s) for s in p["window_strides"]],
                dilations=[int(d) for d in p["rhs_dilation"]],
                pads=pads, group=int(p["feature_group_count"])))
        elif prim == "reduce_window_max":
            wd = p["window_dimensions"]
            ws = p["window_strides"]
            if wd[0] != 1 or wd[1] != 1 or ws[0] != 1 or ws[1] != 1 \
                    or any(x != 0 for pr in p["padding"][:2]
                           for x in pr) \
                    or any(d != 1 for d in p["base_dilation"]) \
                    or any(d != 1 for d in p["window_dilation"]):
                raise NotImplementedError(
                    "onnx export: reduce_window_max beyond NCHW "
                    "spatial max-pooling")
            pads = [int(lo) for lo, _ in p["padding"][2:]] \
                + [int(hi) for _, hi in p["padding"][2:]]
            nodes.append(_node(
                "MaxPool", ins, outs,
                kernel_shape=[int(d) for d in wd[2:]],
                strides=[int(s) for s in ws[2:]], pads=pads))
        elif prim == "concatenate":
            nodes.append(_node("Concat", ins, outs,
                               axis=int(p["dimension"])))
        elif prim == "pad":
            cfg = p["padding_config"]
            if any(int(i) != 0 for _, _, i in cfg):
                raise NotImplementedError(
                    "onnx export: interior (dilating) pad")
            if any(int(lo) < 0 or int(hi) < 0 for lo, hi, _ in cfg):
                raise NotImplementedError("onnx export: negative pad")
            pads = [int(lo) for lo, _, _ in cfg] \
                + [int(hi) for hi in (h for _, h, _ in cfg)]
            cn = fresh("pads")
            inits.append(_tensor_proto(cn, np.asarray(pads, np.int64)))
            # ins = (operand, pad_value); ONNX: (data, pads, value)
            nodes.append(_node("Pad", [ins[0], cn, ins[1]], outs,
                               mode=b"constant"))
        elif prim == "slice":
            if p["strides"] is None:
                steps = [1] * len(p["start_indices"])
            else:
                steps = [int(s) for s in p["strides"]]
            names = []
            for base, arr in (("starts", p["start_indices"]),
                              ("ends", p["limit_indices"]),
                              ("axes", range(len(steps))),
                              ("steps", steps)):
                cn = fresh(base)
                inits.append(_tensor_proto(
                    cn, np.asarray(list(arr), np.int64)))
                names.append(cn)
            nodes.append(_node("Slice", [ins[0]] + names, outs))
        elif prim == "dynamic_slice":
            data, starts_in = ins[0], ins[1:]
            sizes = [int(s) for s in p["slice_sizes"]]
            uns = []
            for s in starts_in:
                c64 = fresh("i64")
                nodes.append(_node("Cast", [s], [c64], to=_DT["int64"]))
                ax = fresh("axis0")
                inits.append(_tensor_proto(
                    ax, np.asarray([0], np.int64)))
                u = fresh("uns")
                nodes.append(_node("Unsqueeze", [c64, ax], [u]))
                uns.append(u)
            starts = fresh("starts")
            nodes.append(_node("Concat", uns, [starts], axis=0))
            sz = fresh("sizes")
            inits.append(_tensor_proto(sz, np.asarray(sizes, np.int64)))
            ends = fresh("ends")
            nodes.append(_node("Add", [starts, sz], [ends]))
            axes = fresh("axes")
            inits.append(_tensor_proto(
                axes, np.arange(len(sizes), dtype=np.int64)))
            nodes.append(_node("Slice", [data, starts, ends, axes],
                               outs))
        elif prim == "gather":
            dn = p["dimension_numbers"]
            op_aval = eqn.invars[0].aval
            idx_aval = eqn.invars[1].aval
            ok = (len(dn.start_index_map) == 1
                  and dn.collapsed_slice_dims == dn.start_index_map
                  and not dn.operand_batching_dims
                  and not dn.start_indices_batching_dims
                  and idx_aval.shape[-1] == 1)
            axis = dn.start_index_map[0] if ok else None
            if ok:
                for d in range(op_aval.ndim):
                    if d != axis and p["slice_sizes"][d] != op_aval.shape[d]:
                        ok = False
                if p["slice_sizes"][axis] != 1:
                    ok = False
            if not ok:
                raise NotImplementedError(
                    "onnx export: gather beyond single-axis take "
                    "(jnp.take/x[idx]) — use format='stablehlo'")
            # jax start_indices carry a trailing length-1 coord dim;
            # ONNX Gather indices are the bare batch shape
            cn = fresh("ishape")
            inits.append(_tensor_proto(
                cn, np.asarray(idx_aval.shape[:-1] or (1,), np.int64)))
            sq = fresh("idx")
            nodes.append(_node("Reshape", [ins[1], cn], [sq]))
            if idx_aval.shape[:-1]:
                nodes.append(_node("Gather", [ins[0], sq], outs,
                                   axis=int(axis)))
            else:
                mid = fresh("g0")
                nodes.append(_node("Gather", [ins[0], sq], [mid],
                                   axis=int(axis)))
                shp = fresh("oshape")
                inits.append(_tensor_proto(
                    shp, np.asarray(eqn.outvars[0].aval.shape,
                                    np.int64)))
                nodes.append(_node("Reshape", [mid, shp], outs))
        elif prim == "argmax":
            # ONNX ArgMax always yields int64; jax's result dtype is
            # the index_dtype (int32 by default) — Cast to keep the
            # declared graph types valid
            mid = fresh("argmax64")
            nodes.append(_node("ArgMax", ins, [mid],
                               axis=int(p["axes"][0]), keepdims=0))
            dt_name = str(np.dtype(eqn.outvars[0].aval.dtype))
            nodes.append(_node("Cast", [mid], outs,
                               to=_DT.get(dt_name, 7)))
        else:
            raise NotImplementedError(
                f"onnx export: unsupported primitive '{prim}' — use "
                "format='stablehlo' for the full-fidelity artifact")
    return nodes, inits, env


def export_onnx(layer, path, input_spec=None, opset_version=None):
    """Trace `layer` and write a real ONNX ModelProto to
    `path + '.onnx'`.  Returns the artifact path."""
    from .jit import _specs_to_avals
    from .framework.tensor import Tensor

    opset = int(opset_version or ONNX_OPSET)
    # the emitted encodings (ReduceSum axes-as-input from 13, Slice
    # input form, Pad value input) are valid for this window; an
    # out-of-range request would silently produce an invalid model
    if not 13 <= opset <= 19:
        raise ValueError(
            f"onnx export: opset_version {opset} unsupported — the "
            "emitted op encodings are valid for opsets 13..19")
    avals = _specs_to_avals(input_spec)
    sd = layer.state_dict()
    names = list(sd.keys())
    vals = [sd[n]._value for n in names]

    def fn(*in_vals):
        from .jit import _swapped_state, _leaves_to_values
        with _swapped_state(layer, names, vals):
            out = layer(*[Tensor(v) for v in in_vals])
        return _leaves_to_values(out)

    closed = jax.make_jaxpr(fn)(*[jnp.zeros(a.shape, a.dtype)
                                  for a in avals])
    in_names = [f"x{i}" for i in range(len(avals))]
    nodes, inits, env = _convert_jaxpr(closed.jaxpr, closed.consts,
                                       in_names, opset=opset)
    from jax._src.core import Literal
    out_names = []
    for i, ov in enumerate(closed.jaxpr.outvars):
        if isinstance(ov, Literal) or ov not in env:
            cn = f"const_out_{i}"
            inits.append(_tensor_proto(
                cn, np.asarray(getattr(ov, "val", 0))))
            nm = f"out_{i}"
            nodes.append(_node("Identity", [cn], [nm]))
        else:
            nm = env[ov]
        out_names.append(nm)

    g = _Proto()
    for n in nodes:
        g.message(1, n)                       # node
    g.string(2, getattr(layer, "__class__").__name__)
    for t in inits:
        g.message(5, t)                       # initializer
    for nm, av in zip(in_names, avals):
        g.message(11, _value_info(nm, av.shape, str(av.dtype)))  # input
    for nm, ov in zip(out_names, closed.jaxpr.outvars):
        g.message(12, _value_info(nm, ov.aval.shape,
                                  str(ov.aval.dtype)))           # output

    opset_msg = _Proto()
    opset_msg.varint(2, opset)               # version
    m = _Proto()
    m.varint(1, ONNX_IR_VERSION)             # ir_version
    m.string(2, "paddle_tpu")                # producer_name
    m.message(7, g)                          # graph
    m.message(8, opset_msg)                  # opset_import
    out_path = path if path.endswith(".onnx") else path + ".onnx"
    with open(out_path, "wb") as f:
        f.write(bytes(m))
    return out_path


def export(layer, path, input_spec=None, opset_version=None,
           format="stablehlo", **configs):
    """Reference signature (onnx/export.py).  format='onnx' writes a
    real ONNX ModelProto (export_onnx — static shapes, core op set);
    the DEFAULT stays the native serialized-StableHLO artifact: it has
    full op fidelity, supports dynamic dims, and round-trips through
    paddle.onnx.load/jit.load, which ONNX protobufs cannot (the
    reference defaults to ONNX because ONNX IS its interchange format;
    here StableHLO is)."""
    if format == "onnx":
        base = path[:-8] if path.endswith(".pdmodel") else path
        return export_onnx(layer, base, input_spec, opset_version)
    from .jit import save as jit_save
    base = path[:-8] if path.endswith(".pdmodel") else path
    jit_save(layer, base, input_spec=input_spec, **configs)
    return base + ".pdmodel"


def load(path):
    """Load a StableHLO artifact back as an executable layer (ONNX
    artifacts are for EXTERNAL consumers; the native loader is
    jit.load)."""
    from .jit import load as jit_load
    base = path[:-8] if path.endswith(".pdmodel") else path
    return jit_load(base)
