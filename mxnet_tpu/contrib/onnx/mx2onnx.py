"""Symbol-DAG -> ONNX exporter.

Reference: python/mxnet/contrib/onnx/mx2onnx/_export_model.py (exporter
driven by per-op translator functions, _op_translations.py).  Same design
here: ``MX2ONNX`` maps registry op names to translators emitting standard
ONNX nodes (opset 17); fused MXNet ops (interleaved self-attention
matmuls, FullyConnected on >2D) are decomposed into
Reshape/Transpose/Slice/MatMul primitives, and value-independent ops
(arange_like) are folded to constant initializers using the statically
known shapes.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import numpy as onp

from . import proto

MX2ONNX: Dict[str, Callable] = {}


def translator(*names):
    def deco(fn):
        for n in names:
            MX2ONNX[n] = fn
        return fn

    return deco


class _Ctx:
    """Per-export state handed to translators."""

    def __init__(self, opset):
        self.nodes: List[bytes] = []
        self.initializers: List[bytes] = []
        self.init_names: set = set()
        self.shapes: Dict[str, tuple] = {}   # onnx tensor name -> shape
        self.opset = opset
        self._uid = 0

    def uid(self, base):
        self._uid += 1
        return f"{base}_{self._uid}"

    def add_node(self, op_type, inputs, outputs, name=None, **attrs):
        self.nodes.append(proto.node(op_type, list(inputs), list(outputs),
                                     name or outputs[0], attrs))

    def add_init(self, name, array):
        if name not in self.init_names:
            self.init_names.add(name)
            self.initializers.append(proto.tensor(name, onp.asarray(array)))
        return name

    def const(self, base, array):
        return self.add_init(self.uid(base), array)


def _pads2(pad):
    ph, pw = (pad if pad else (0, 0))
    return [int(ph), int(pw), int(ph), int(pw)]


@translator("Convolution")
def _conv(node, ins, outs, ctx):
    a = node.attrs
    attrs = dict(kernel_shape=[int(k) for k in a.get("kernel", ())],
                 strides=[int(s) for s in a.get("stride", (1, 1))],
                 pads=_pads2(a.get("pad")),
                 dilations=[int(d) for d in a.get("dilate", (1, 1))],
                 group=int(a.get("num_group", 1)))
    ctx.add_node("Conv", ins, outs, **attrs)


@translator("Deconvolution")
def _deconv(node, ins, outs, ctx):
    a = node.attrs
    ctx.add_node("ConvTranspose", ins, outs,
                 kernel_shape=[int(k) for k in a.get("kernel", ())],
                 strides=[int(s) for s in a.get("stride", (1, 1))],
                 pads=_pads2(a.get("pad")),
                 group=int(a.get("num_group", 1)))


@translator("BatchNorm")
def _bn(node, ins, outs, ctx):
    a = node.attrs
    ctx.add_node("BatchNormalization", ins[:5], outs[:1],
                 epsilon=float(a.get("eps", 1e-3)),
                 momentum=float(a.get("momentum", 0.9)))


@translator("Activation")
def _act(node, ins, outs, ctx):
    table = {"relu": "Relu", "sigmoid": "Sigmoid", "tanh": "Tanh",
             "softrelu": "Softplus", "softsign": "Softsign"}
    ctx.add_node(table[node.attrs.get("act_type", "relu")], ins, outs)


@translator("relu")
def _relu(node, ins, outs, ctx):
    ctx.add_node("Relu", ins, outs)


@translator("sigmoid")
def _sigmoid(node, ins, outs, ctx):
    ctx.add_node("Sigmoid", ins, outs)


@translator("tanh")
def _tanh(node, ins, outs, ctx):
    ctx.add_node("Tanh", ins, outs)


@translator("LeakyReLU")
def _leaky(node, ins, outs, ctx):
    a = node.attrs
    act = a.get("act_type", "leaky")
    if act == "leaky":
        ctx.add_node("LeakyRelu", ins[:1], outs,
                     alpha=float(a.get("slope", 0.25)))
    elif act == "elu":
        ctx.add_node("Elu", ins[:1], outs, alpha=float(a.get("slope", 0.25)))
    elif act == "prelu":
        ctx.add_node("PRelu", ins[:2], outs)
    elif act == "gelu":
        # exact gelu: 0.5 * x * (1 + erf(x / sqrt(2)))
        x = ins[0]
        s = ctx.const("gelu_sqrt2", onp.asarray(math.sqrt(2.0), onp.float32))
        half = ctx.const("gelu_half", onp.asarray(0.5, onp.float32))
        one = ctx.const("gelu_one", onp.asarray(1.0, onp.float32))
        d = ctx.uid("gelu_div")
        ctx.add_node("Div", [x, s], [d])
        e = ctx.uid("gelu_erf")
        ctx.add_node("Erf", [d], [e])
        p = ctx.uid("gelu_1p")
        ctx.add_node("Add", [e, one], [p])
        m = ctx.uid("gelu_xm")
        ctx.add_node("Mul", [x, p], [m])
        ctx.add_node("Mul", [m, half], outs)
    else:
        raise ValueError(f"LeakyReLU act_type {act} not exportable")


@translator("Pooling")
def _pool(node, ins, outs, ctx):
    a = node.attrs
    ptype = a.get("pool_type", "max")
    if a.get("global_pool", False):
        op = {"max": "GlobalMaxPool", "avg": "GlobalAveragePool"}[ptype]
        ctx.add_node(op, ins, outs)
        return
    op = {"max": "MaxPool", "avg": "AveragePool"}[ptype]
    attrs = dict(kernel_shape=[int(k) for k in a.get("kernel", (1, 1))],
                 strides=[int(s) for s in a.get("stride") or (1, 1)],
                 pads=_pads2(a.get("pad")))
    if op == "AveragePool":
        attrs["count_include_pad"] = int(a.get("count_include_pad", True))
    if a.get("pooling_convention", "valid") == "full":
        attrs["ceil_mode"] = 1
    ctx.add_node(op, ins, outs, **attrs)


@translator("FullyConnected")
def _fc(node, ins, outs, ctx):
    a = node.attrs
    no_bias = a.get("no_bias", False)
    data, weight = ins[0], ins[1]
    rank = len(ctx.shapes.get(data, (2,)))
    flatten = a.get("flatten", True)
    if flatten and rank != 2:
        f = ctx.uid("flat")
        ctx.add_node("Flatten", [data], [f], axis=1)
        data = f
        rank = 2
    if rank == 2:
        ins2 = [data, weight] + ([] if no_bias else [ins[2]])
        ctx.add_node("Gemm", ins2, outs, alpha=1.0, beta=1.0, transA=0,
                     transB=1)
    else:
        # flatten=False on ND input: MatMul with pre-transposed weight
        wt = ctx.uid(weight + "_T")
        ctx.add_node("Transpose", [weight], [wt], perm=[1, 0])
        mm = ctx.uid("fc_mm") if not no_bias else outs[0]
        ctx.add_node("MatMul", [data, wt], [mm])
        if not no_bias:
            ctx.add_node("Add", [mm, ins[2]], outs)


@translator("broadcast_add", "elemwise_add", "_plus")
def _add(node, ins, outs, ctx):
    ctx.add_node("Add", ins, outs)


@translator("broadcast_sub", "elemwise_sub")
def _sub(node, ins, outs, ctx):
    ctx.add_node("Sub", ins, outs)


@translator("broadcast_mul", "elemwise_mul")
def _mul(node, ins, outs, ctx):
    ctx.add_node("Mul", ins, outs)


@translator("broadcast_div", "elemwise_div")
def _div(node, ins, outs, ctx):
    ctx.add_node("Div", ins, outs)


@translator("add_n")
def _addn(node, ins, outs, ctx):
    ctx.add_node("Sum", ins, outs)


@translator("flatten", "Flatten")
def _flatten(node, ins, outs, ctx):
    ctx.add_node("Flatten", ins, outs, axis=1)


@translator("softmax")
def _softmax(node, ins, outs, ctx):
    ctx.add_node("Softmax", ins[:1], outs,
                 axis=int(node.attrs.get("axis", -1)))


@translator("LayerNorm")
def _ln(node, ins, outs, ctx):
    a = node.attrs
    ctx.add_node("LayerNormalization", ins[:3], outs[:1],
                 axis=int(a.get("axis", -1)),
                 epsilon=float(a.get("eps", 1e-5)))


@translator("embedding", "Embedding")
def _embed(node, ins, outs, ctx):
    # mxnet: (indices, weight); onnx Gather: (data=weight, indices)
    idx64 = ctx.uid("idx64")
    ctx.add_node("Cast", [ins[0]], [idx64], to=proto.INT64)
    ctx.add_node("Gather", [ins[1], idx64], outs, axis=0)


@translator("transpose")
def _transpose(node, ins, outs, ctx):
    axes = node.attrs.get("axes")
    if axes:
        ctx.add_node("Transpose", ins, outs, perm=[int(x) for x in axes])
    else:
        ctx.add_node("Transpose", ins, outs)


@translator("reshape", "Reshape")
def _reshape(node, ins, outs, ctx):
    shape = [int(s) for s in node.attrs.get("shape", ())]
    shp = ctx.const("shape", onp.asarray(shape, onp.int64))
    ctx.add_node("Reshape", [ins[0], shp], outs)


@translator("Dropout")
def _dropout(node, ins, outs, ctx):
    ctx.add_node("Identity", ins[:1], outs[:1])   # inference export


@translator("Concat", "concat")
def _concat(node, ins, outs, ctx):
    ctx.add_node("Concat", ins, outs,
                 axis=int(node.attrs.get("dim", node.attrs.get("axis", 1))))


@translator("arange_like")
def _arange_like(node, ins, outs, ctx):
    """Value-independent: fold to a constant from the static shape."""
    from ...ops.registry import get_op

    shape = ctx.shapes[ins[0]]
    val = get_op("arange_like").fn(onp.zeros(shape, onp.float32),
                                   **node.attrs)
    ctx.add_init(outs[0], onp.asarray(val, onp.float32))


def _slice_qkv(ctx, x5, which, name, S, B, H, hd):
    """Slice [S,B,H,3,hd] at index ``which`` on axis 3 -> [S,B,H,hd]."""
    st = ctx.const("st", onp.asarray([which], onp.int64))
    en = ctx.const("en", onp.asarray([which + 1], onp.int64))
    ax = ctx.const("ax", onp.asarray([3], onp.int64))
    sl = ctx.uid(name + "_sl")
    ctx.add_node("Slice", [x5, st, en, ax], [sl])
    shp = ctx.const("shp", onp.asarray([S, B, H, hd], onp.int64))
    out = ctx.uid(name)
    ctx.add_node("Reshape", [sl, shp], [out])
    return out


def _sbhd_to_bh_s_d(ctx, x, name, S, B, H, hd):
    t = ctx.uid(name + "_t")
    ctx.add_node("Transpose", [x], [t], perm=[1, 2, 0, 3])
    shp = ctx.const("shp", onp.asarray([B * H, S, hd], onp.int64))
    out = ctx.uid(name + "_r")
    ctx.add_node("Reshape", [t, shp], [out])
    return out


@translator("interleaved_matmul_selfatt_qk")
def _att_qk(node, ins, outs, ctx):
    """(S,B,3E) interleaved qkv -> (B*H, S, S) scaled QK^T, decomposed to
    Reshape/Slice/Transpose/MatMul (reference contrib/transformer.cc:650)."""
    S, B, E3 = ctx.shapes[ins[0]]
    H = int(node.attrs.get("heads", 1))
    hd = E3 // 3 // H
    shp5 = ctx.const("shp5", onp.asarray([S, B, H, 3, hd], onp.int64))
    x5 = ctx.uid("qkv5")
    ctx.add_node("Reshape", [ins[0], shp5], [x5])
    q = _slice_qkv(ctx, x5, 0, "q", S, B, H, hd)
    k = _slice_qkv(ctx, x5, 1, "k", S, B, H, hd)
    qb = _sbhd_to_bh_s_d(ctx, q, "qb", S, B, H, hd)
    kb = _sbhd_to_bh_s_d(ctx, k, "kb", S, B, H, hd)
    scale = ctx.const("scale",
                      onp.asarray(1.0 / math.sqrt(hd), onp.float32))
    qs = ctx.uid("q_scaled")
    ctx.add_node("Mul", [qb, scale], [qs])
    kt = ctx.uid("k_T")
    ctx.add_node("Transpose", [kb], [kt], perm=[0, 2, 1])
    ctx.add_node("MatMul", [qs, kt], outs)


@translator("interleaved_matmul_selfatt_valatt")
def _att_valatt(node, ins, outs, ctx):
    """attention (B*H,S,S) x V from interleaved qkv -> (S,B,E)."""
    S, B, E3 = ctx.shapes[ins[0]]
    H = int(node.attrs.get("heads", 1))
    hd = E3 // 3 // H
    shp5 = ctx.const("shp5", onp.asarray([S, B, H, 3, hd], onp.int64))
    x5 = ctx.uid("qkv5")
    ctx.add_node("Reshape", [ins[0], shp5], [x5])
    v = _slice_qkv(ctx, x5, 2, "v", S, B, H, hd)
    vb = _sbhd_to_bh_s_d(ctx, v, "vb", S, B, H, hd)
    mm = ctx.uid("att_v")
    ctx.add_node("MatMul", [ins[1], vb], [mm])
    shp4 = ctx.const("shp4", onp.asarray([B, H, S, hd], onp.int64))
    r4 = ctx.uid("att_r4")
    ctx.add_node("Reshape", [mm, shp4], [r4])
    t = ctx.uid("att_t")
    ctx.add_node("Transpose", [r4], [t], perm=[2, 0, 1, 3])
    shp3 = ctx.const("shp3", onp.asarray([S, B, H * hd], onp.int64))
    ctx.add_node("Reshape", [t, shp3], outs)


@translator("interleaved_selfatt")
def _att_core(node, ins, outs, ctx):
    """The attention core as inference runs it: the two interleaved
    decompositions around a Softmax (the probability dropout is a
    training-only term, and its key input is not exported)."""
    scores, probs = ctx.uid("att_scores"), ctx.uid("att_probs")
    _att_qk(node, ins[:1], [scores], ctx)
    ctx.add_node("Softmax", [scores], [probs], axis=-1)
    _att_valatt(node, [ins[0], probs], outs, ctx)


@translator("dot", "linalg_gemm2", "batch_dot")
def _matmul(node, ins, outs, ctx):
    ctx.add_node("MatMul", ins, outs)


@translator("mean")
def _mean(node, ins, outs, ctx):
    a = node.attrs
    ax = a.get("axis")
    attrs = {"keepdims": int(a.get("keepdims", False))}
    if ax is not None:
        attrs["axes"] = [int(x) for x in (ax if isinstance(ax, (tuple, list))
                                          else (ax,))]
    ctx.add_node("ReduceMean", ins, outs, **attrs)


# ---------------------------------------------------------------------------


def export_model(sym, params, in_shapes=None, in_types=None,
                 onnx_file_path="model.onnx", opset_version=17,
                 model_name="mxnet_tpu_model"):
    """Export a traced Symbol + params to an ONNX file
    (reference mx2onnx/_export_model.py export_model).

    ``params``: {name: NDArray | jax/numpy array}.  ``in_shapes``: shapes
    for the non-parameter inputs, in ``sym.list_inputs()`` order.  Returns
    the path.
    """
    import jax

    from ...ops.registry import get_op

    param_arrays = {}
    for k, v in (params or {}).items():
        arr = v.asnumpy() if hasattr(v, "asnumpy") else onp.asarray(v)
        param_arrays[k.split(":", 1)[-1]] = arr

    nodes = sym._topo() if hasattr(sym, "_topo") else None
    if nodes is None:
        # topological walk over the DAG
        seen, nodes = set(), []

        def walk(n):
            if id(n) in seen:
                return
            seen.add(id(n))
            for (src, _i) in n.inputs:
                walk(src)
            nodes.append(n)

        for (n, _i) in sym._outputs:
            walk(n)

    data_inputs = [n.name for n in nodes
                   if n.op is None and n.name not in param_arrays]
    in_shapes = list(in_shapes or [])
    in_types = list(in_types or ["float32"] * len(data_inputs))
    if len(in_shapes) != len(data_inputs):
        raise ValueError(
            f"need shapes for inputs {data_inputs}, got {in_shapes}")

    ctx = _Ctx(opset_version)

    # ---- static shape propagation (abstract eval per node) --------------
    import jax.numpy as jnp

    name_of: Dict[Any, List[str]] = {}
    aval: Dict[str, Any] = {}

    def out_names(n):
        if n.num_outputs == 1:
            return [n.name]
        return [f"{n.name}:{i}" for i in range(n.num_outputs)]

    for n in nodes:
        name_of[id(n)] = out_names(n)
    for n in nodes:
        if n.op is None:
            if n.name in param_arrays:
                arr = param_arrays[n.name]
                sds = jax.ShapeDtypeStruct(arr.shape, arr.dtype)
            else:
                i = data_inputs.index(n.name)
                sds = jax.ShapeDtypeStruct(
                    tuple(in_shapes[i]), onp.dtype(in_types[i]))
            aval[n.name] = sds
            ctx.shapes[n.name] = tuple(sds.shape)
            continue
        schema = get_op(n.op)
        ins_av = [aval[name_of[id(src)][i]] for (src, i) in n.inputs]
        if schema.num_inputs == -1:
            out = jax.eval_shape(lambda *a: schema.fn(list(a), **n.attrs),
                                 *ins_av)
        else:
            out = jax.eval_shape(lambda *a: schema.fn(*a, **n.attrs),
                                 *ins_av)
        outs = list(out) if isinstance(out, (list, tuple)) else [out]
        for nm, o in zip(name_of[id(n)], outs):
            aval[nm] = o
            ctx.shapes[nm] = tuple(o.shape)

    # ---- translate -------------------------------------------------------
    for n in nodes:
        if n.op is None:
            if n.name in param_arrays:
                ctx.add_init(n.name, param_arrays[n.name])
            continue
        if n.op not in MX2ONNX:
            raise NotImplementedError(
                f"no ONNX translator for op '{n.op}' (node {n.name}); "
                f"supported: {sorted(MX2ONNX)}")
        ins = [name_of[id(src)][i] for (src, i) in n.inputs]
        MX2ONNX[n.op](n, ins, name_of[id(n)], ctx)

    g_inputs = [
        proto.value_info(nm, proto.NP_TO_ONNX[onp.dtype(dt)], tuple(shp))
        for nm, shp, dt in zip(data_inputs, in_shapes, in_types)
    ]
    g_outputs = []
    for (n, i) in sym._outputs:
        nm = name_of[id(n)][i]
        g_outputs.append(proto.value_info(
            nm, proto.NP_TO_ONNX[onp.dtype(str(aval[nm].dtype))],
            tuple(aval[nm].shape)))

    gb = proto.graph(ctx.nodes, model_name, ctx.initializers, g_inputs,
                     g_outputs)
    mb = proto.model(gb, opset=opset_version)
    with open(onnx_file_path, "wb") as f:
        f.write(mb)
    return onnx_file_path
