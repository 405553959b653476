"""INT8 quantized inference: calibrate -> convert -> run.

Reference: ``src/operator/quantization/`` (quantize/dequantize/requantize
ops, quantized conv/fc kernels, calibrate.cc's naive/entropy threshold
selection, and quantize_graph_pass.cc's graph rewrite that wraps
quantizable nodes in quantize/dequantize pairs; python driver
python/mxnet/contrib/quantization.py quantize_model).

TPU-native design: the graph rewrite happens on the Symbol DAG (the same
artifact hybridize traces), and the quantized kernels are XLA lowerings
that keep the s8 x s8 -> s32 matmul/conv on the MXU with per-tensor
scales applied as cheap epilogues — XLA fuses the dequantize into the
surrounding elementwise work.  Activation ranges come from running the
fp32 graph on calibration batches and recording per-node output ranges
(naive min/max or percentile clipping, the entropy-lite analog).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from ..ops.registry import register

__all__ = ["quantize", "dequantize", "requantize", "collect_calib_ranges",
           "quantize_symbol", "quantize_net", "QuantizedNet"]

INT8_MIN, INT8_MAX = -127.0, 127.0       # symmetric, matches reference


# ---------------------------------------------------------------------------
# ops (reference quantize.cc / dequantize.cc / requantize.cc)
# ---------------------------------------------------------------------------

@register("quantize", num_inputs=1, num_outputs=-1, differentiable=False)
def quantize(data, min_range=-1.0, max_range=1.0, out_type="int8"):
    """fp32 -> int8 with symmetric scale from the calibrated range
    (reference quantize_v2 with min/max_calib_range)."""
    scale = INT8_MAX / jnp.maximum(jnp.maximum(abs(float(min_range)),
                                               abs(float(max_range))),
                                   1e-12)
    q = jnp.clip(jnp.round(data * scale), INT8_MIN, INT8_MAX).astype(
        jnp.int8)
    return (q, jnp.float32(min_range), jnp.float32(max_range))


@register("dequantize", num_inputs=3, differentiable=False)
def dequantize(qdata, min_range, max_range, out_type="float32"):
    scale = jnp.maximum(jnp.maximum(jnp.abs(min_range),
                                    jnp.abs(max_range)), 1e-12) / INT8_MAX
    return qdata.astype(jnp.float32) * scale


@register("requantize", num_inputs=3, num_outputs=-1, differentiable=False)
def requantize(qdata32, min_range, max_range, min_calib_range=None,
               max_calib_range=None):
    """int32 accumulator -> int8 with a new scale (reference
    requantize.cc)."""
    in_scale = jnp.maximum(jnp.maximum(jnp.abs(min_range),
                                       jnp.abs(max_range)), 1e-12) / (
        INT8_MAX * INT8_MAX)
    f = qdata32.astype(jnp.float32) * in_scale
    lo = float(min_calib_range if min_calib_range is not None else -1.0)
    hi = float(max_calib_range if max_calib_range is not None else 1.0)
    out_scale = INT8_MAX / max(abs(lo), abs(hi), 1e-12)
    q = jnp.clip(jnp.round(f * out_scale), INT8_MIN, INT8_MAX).astype(
        jnp.int8)
    return (q, jnp.float32(lo), jnp.float32(hi))


def _sym_scale(lo: float, hi: float) -> float:
    return max(abs(lo), abs(hi), 1e-12) / INT8_MAX


def _quantized_epilogue(out, fused_relu, out_min, out_max):
    """Shared epilogue: optional fused relu, then optional fused
    REQUANTIZE (the reference's quantize_graph_pass.cc requantize-fusion):
    when the consumer is another quantized kernel, emit int8 directly at
    the consumer's calibrated scale instead of fp32 -> separate quantize
    node.  Halves the node count of deep int8 graphs (and their compile
    time)."""
    if fused_relu:
        out = jnp.maximum(out, 0)
    if out_min is not None and out_max is not None:
        scale = INT8_MAX / max(abs(float(out_min)), abs(float(out_max)),
                               1e-12)
        out = jnp.clip(jnp.round(out * scale), INT8_MIN, INT8_MAX).astype(
            jnp.int8)
    return out


@register("quantized_fully_connected", num_inputs=-1, differentiable=False)
def quantized_fully_connected(arrays, num_hidden=0, no_bias=False,
                              flatten=True, data_scale=1.0, w_scale=1.0,
                              fused_relu=False, out_min=None, out_max=None):
    """s8 data x s8 weight -> s32 on the MXU, fp32 epilogue (reference
    quantized_fully_connected.cc).  arrays = [qdata, qweight, (bias fp32)]."""
    qd, qw = arrays[0], arrays[1]
    if flatten and qd.ndim > 2:
        qd = qd.reshape(qd.shape[0], -1)
    acc = jax.lax.dot_general(
        qd, qw, (((qd.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (data_scale * w_scale)
    if not no_bias and len(arrays) > 2:
        out = out + arrays[2]
    return _quantized_epilogue(out, fused_relu, out_min, out_max)


@register("quantized_conv", num_inputs=-1, differentiable=False)
def quantized_conv(arrays, kernel=(1, 1), stride=(1, 1), dilate=(1, 1),
                   pad=(0, 0), num_filter=1, num_group=1, no_bias=False,
                   layout=None, data_scale=1.0, w_scale=1.0,
                   fused_relu=False, out_min=None, out_max=None):
    """s8 conv with s32 accumulation (reference quantized_conv.cc).

    Layout-general like the fp32 Convolution op: the NHWC fast path the
    bench uses quantizes without relayouts (weights stay in the layout the
    fp32 model trained in — O is axis 0 for both OIHW and OHWI, so the
    offline weight quantization is layout-independent)."""
    from ..ops.nn import (_conv_dimension_numbers, _tup,
                          maybe_pad_conv_channels)

    qd, qw = arrays[0], arrays[1]
    nsp = len(kernel)
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nsp]
    stride = _tup(stride, nsp) if stride else (1,) * nsp
    dilate = _tup(dilate, nsp) if dilate else (1,) * nsp
    pad = _tup(pad, nsp) if pad else (0,) * nsp

    qd = qd.astype(jnp.int8)
    qw = qw.astype(jnp.int8)
    # MXU-alignment padding pass (ops/nn.py): int8 sublane quantum is 32,
    # so misaligned channel axes pad with zero taps (exact in integer
    # math) and Cout slices back below
    c_axis = layout.index("C")
    true_cout = None
    padded = maybe_pad_conv_channels(qd, qw, layout, num_group)
    if padded is not None:
        qd, qw, true_cout = padded
    dn = jax.lax.conv_dimension_numbers(
        qd.shape, qw.shape, _conv_dimension_numbers(layout))
    out = jax.lax.conv_general_dilated(
        qd, qw,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, feature_group_count=num_group,
        dimension_numbers=dn,
        preferred_element_type=jnp.int32)
    if true_cout is not None and out.shape[c_axis] != true_cout:
        out = jax.lax.slice_in_dim(out, 0, true_cout, axis=c_axis)
    out = out.astype(jnp.float32) * (data_scale * w_scale)
    if not no_bias and len(arrays) > 2:
        shape = [1] * out.ndim
        shape[c_axis] = arrays[2].shape[0]
        out = out + arrays[2].reshape(shape)
    return _quantized_epilogue(out, fused_relu, out_min, out_max)


# ---------------------------------------------------------------------------
# calibration (reference calibrate.cc + quantize_model driver)
# ---------------------------------------------------------------------------

def collect_calib_ranges(sym, feeds: List[Dict[str, Any]],
                         mode: str = "naive",
                         percentile: float = 99.99) -> Dict[str, Tuple[float,
                                                                       float]]:
    """Run the fp32 graph on calibration batches and record per-node output
    ranges.  ``mode='naive'`` = min/max (reference CalibrationNaive);
    ``'percentile'`` clips outliers (the entropy-lite analog of
    CalibrationEntropy)."""
    from ..symbol.symbol import execute_graph

    nodes = sym._topo()
    entries = [(n, i) for n in nodes if n.op is not None
               for i in range(n.num_outputs)]
    names = [_out_name(n, i) for (n, i) in entries]
    ranges: Dict[str, Tuple[float, float]] = {}
    for feed in feeds:
        feed = {k: (v._data if hasattr(v, "_data") else jnp.asarray(v))
                for k, v in feed.items()}
        outs = execute_graph(entries, feed)
        for name, o in zip(names, outs):
            if not jnp.issubdtype(o.dtype, jnp.floating):
                continue
            v = onp.asarray(o, onp.float32).reshape(-1)
            if mode == "percentile":
                lo = float(onp.percentile(v, 100.0 - percentile))
                hi = float(onp.percentile(v, percentile))
            else:
                lo, hi = float(v.min()), float(v.max())
            if name in ranges:
                plo, phi = ranges[name]
                ranges[name] = (min(lo, plo), max(hi, phi))
            else:
                ranges[name] = (lo, hi)
    return ranges


def _out_name(n, i):
    return n.name if n.num_outputs == 1 else f"{n.name}:{i}"


# ---------------------------------------------------------------------------
# graph rewrite (reference quantize_graph_pass.cc)
# ---------------------------------------------------------------------------

QUANTIZABLE = {"Convolution", "FullyConnected"}


def _consumer_map(sym):
    """id(node) -> [(consumer_node, input_pos)] plus head multiplicity."""
    cons: Dict[int, list] = {}
    heads: Dict[int, int] = {}
    for n in sym._topo():
        for pos, (src, _i) in enumerate(n.inputs):
            cons.setdefault(id(src), []).append((n, pos))
    for (h, _i) in sym._outputs:
        heads[id(h)] = heads.get(id(h), 0) + 1
    return cons, heads


def _constant_fold(sym, param_arrays: Dict[str, onp.ndarray]):
    """Evaluate param-only subtrees offline and replace them with new
    params (reference analog: the MKLDNN subgraph fuser sees weights as
    constants; here e.g. the space-to-depth stem re-expresses conv0's
    weight as reshape/transpose ops over the stored param, which must
    collapse back to a plain variable for the BN fold and offline weight
    quantization to see a Convolution fed by a param).  Returns
    (new_sym, new_params)."""
    from ..symbol.symbol import SymNode, Symbol, execute_graph

    nodes = sym._topo()
    const: Dict[int, bool] = {}
    for n in nodes:
        if n.op is None:
            const[id(n)] = n.name in param_arrays
        else:
            det = not any(k in n.op.lower()
                          for k in ("rand", "dropout", "sample"))
            const[id(n)] = (det and bool(n.inputs)
                            and all(const[id(s)] for (s, _i) in n.inputs))
    cons, heads = _consumer_map(sym)
    frontier = [n for n in nodes if n.op is not None and const[id(n)]
                and (id(n) in heads
                     or any(not const[id(u)]
                            for (u, _p) in cons.get(id(n), [])))]
    if not frontier:
        return sym, param_arrays
    entries = [(n, i) for n in frontier for i in range(n.num_outputs)]
    outs = execute_graph(entries, {k: jnp.asarray(v)
                                   for k, v in param_arrays.items()})
    new_params = dict(param_arrays)
    repl: Dict[Tuple[int, int], SymNode] = {}
    for (n, i), o in zip(entries, outs):
        name = f"{n.name}_const" + (str(i) if n.num_outputs > 1 else "")
        while name in new_params:
            name += "_"
        new_params[name] = onp.asarray(o)
        repl[(id(n), i)] = SymNode(None, name, {}, [])
    cache: Dict[int, SymNode] = {}

    def rebuild(n) -> SymNode:
        got = cache.get(id(n))
        if got is not None:
            return got
        ins = []
        for (src, i) in n.inputs:
            r = repl.get((id(src), i))
            ins.append((r, 0) if r is not None else (rebuild(src), i))
        out = SymNode(n.op, n.name, dict(n.attrs), ins, n.num_outputs)
        out.attr_dict = dict(n.attr_dict)     # keep AttrScope/__shape__
        cache[id(n)] = out
        return out

    new_outputs = [((repl[(id(n), i)], 0) if (id(n), i) in repl
                    else (rebuild(n), i)) for (n, i) in sym._outputs]
    return Symbol(new_outputs), new_params


def _fold_bn_relu(sym, param_arrays: Dict[str, onp.ndarray]):
    """Inference-graph fusion BEFORE quantization (the reference reaches
    the same shape through the MKLDNN subgraph fuser + quantize pass:
    conv+bn+relu collapses to one conv with folded weights and a relu
    epilogue).  BatchNorm running stats fold into the conv's weight/bias:

        w'[c] = w[c] * gamma_c / sqrt(var_c + eps)
        b'[c] = (b[c] - mean_c) * gamma_c / sqrt(var_c + eps) + beta_c

    The folded node takes the name of the LAST fused op so downstream
    calibrated-range lookups keyed by original output names still hit.
    Only single-consumer chains fold (a second consumer still needs the
    unfused intermediate).  Returns (new_sym, new_params).
    """
    from ..symbol.symbol import SymNode, Symbol

    cons, heads = _consumer_map(sym)
    new_params = dict(param_arrays)

    def _single_consumer(n):
        return len(cons.get(id(n), [])) == 1 and id(n) not in heads

    cache: Dict[int, SymNode] = {}

    def fold(n) -> SymNode:
        got = cache.get(id(n))
        if got is not None:
            return got
        new_inputs = [(fold(src), i) for (src, i) in n.inputs]
        out = None
        if (n.op == "BatchNorm" and len(n.inputs) == 5
                and not n.attrs.get("training")
                and not n.attrs.get("output_mean_var")):
            conv_orig, _ci = n.inputs[0]
            conv_new = new_inputs[0][0]
            # the BN must normalize the conv's output-channel axis (axis 1
            # for NCHW, 3 for NHWC); the per-channel fold math itself is
            # layout-independent because O is axis 0 of the weight either way
            conv_layout = (conv_new.attrs.get("layout") or "NCHW"
                           if conv_new.op == "Convolution" else "NCHW")
            axis_ok = int(n.attrs.get("axis", 1)) == conv_layout.index("C")
            stat_names = [s.name for (s, _j) in n.inputs[1:]]
            w_ok = (axis_ok
                    and conv_new.op == "Convolution"
                    and len(conv_new.inputs) >= 2
                    and conv_new.inputs[1][0].op is None
                    and conv_new.inputs[1][0].name in new_params
                    and (conv_new.attrs.get("no_bias", False)
                         or len(conv_new.inputs) < 3
                         or (conv_new.inputs[2][0].op is None
                             and conv_new.inputs[2][0].name in new_params)))
            if (w_ok and _single_consumer(conv_orig)
                    and all(s in new_params for s in stat_names)):
                g, beta, mean, var = (new_params[s] for s in stat_names)
                if n.attrs.get("fix_gamma", True):
                    g = onp.ones_like(g)
                eps = float(n.attrs.get("eps", 1e-3))
                scale = g / onp.sqrt(var + eps)
                w_name = conv_new.inputs[1][0].name
                w = new_params[w_name]
                if conv_new.attrs.get("no_bias", False) \
                        or len(conv_new.inputs) < 3:
                    b = onp.zeros(w.shape[0], w.dtype)
                else:
                    b = new_params[conv_new.inputs[2][0].name]
                wf = (w * scale.reshape((-1,) + (1,) * (w.ndim - 1))) \
                    .astype(w.dtype)
                bf = ((b - mean) * scale + beta).astype(w.dtype)
                wf_name, bf_name = n.name + "_wfold", n.name + "_bfold"
                new_params[wf_name] = wf
                new_params[bf_name] = bf
                attrs = dict(conv_new.attrs)
                attrs["no_bias"] = False
                out = SymNode("Convolution", n.name, attrs,
                              [conv_new.inputs[0],
                               (SymNode(None, wf_name, {}, []), 0),
                               (SymNode(None, bf_name, {}, []), 0)],
                              num_outputs=1)
                out.attrs["_bn_folded"] = True
        elif ((n.op == "Activation"
               and n.attrs.get("act_type", "relu") == "relu")
              or n.op == "relu"):
            src_orig, _si = n.inputs[0]
            src_new = new_inputs[0][0]
            if (src_new.op in QUANTIZABLE
                    and src_new.attrs.get("_bn_folded")
                    and _single_consumer(src_orig)):
                attrs = dict(src_new.attrs)
                attrs["fused_relu"] = True
                out = SymNode(src_new.op, n.name, attrs,
                              list(src_new.inputs), num_outputs=1)
        if out is None:
            out = SymNode(n.op, n.name, dict(n.attrs), new_inputs,
                          n.num_outputs)
            out.attr_dict = dict(n.attr_dict)
        cache[id(n)] = out
        return out

    new_sym = Symbol([(fold(n), i) for (n, i) in sym._outputs])
    # the internal marker must not leak into serialized graphs
    for n in new_sym._topo():
        n.attrs.pop("_bn_folded", None)
    return new_sym, new_params


def _fuse_requantize(sym) -> int:
    """Reference quantize_graph_pass.cc requantize-fusion, TPU shape:
    when EVERY consumer of a quantized kernel is a `quantize` node with
    one identical calibrated range, emit int8 from the kernel's epilogue
    (out_min/out_max attrs) and delete the quantize nodes.  Mutates the
    graph in place; returns the number of kernels fused."""
    cons, heads = _consumer_map(sym)
    fused = 0
    for n in sym._topo():
        if n.op not in ("quantized_conv", "quantized_fully_connected"):
            continue
        if id(n) in heads:
            continue
        users = cons.get(id(n), [])
        if not users or not all(u.op == "quantize" for (u, _p) in users):
            continue
        if any(id(u) in heads for (u, _p) in users):
            continue          # a head quantize node must keep quantizing
        ranges = {(float(u.attrs.get("min_range", -1.0)),
                   float(u.attrs.get("max_range", 1.0)))
                  for (u, _p) in users}
        if len(ranges) != 1:
            continue
        (lo, hi), = ranges
        n.attrs["out_min"], n.attrs["out_max"] = lo, hi
        for (q, _p) in users:
            for (c2, p2) in cons.get(id(q), []):
                c2.inputs[p2] = (n, 0)
        fused += 1
    return fused


def quantize_symbol(sym, params: Dict[str, Any],
                    calib_ranges: Dict[str, Tuple[float, float]],
                    quantized_dtype: str = "int8",
                    excluded_names: Tuple[str, ...] = ()):
    """Rewrite a Symbol: every quantizable node whose input range was
    calibrated becomes a quantized kernel fed by int8 weights (offline
    quantized here) and int8 activations (quantized at run time with the
    calibrated scale).  Returns (new_sym, new_params).

    Mirrors quantize_graph_pass.cc: nodes not in QUANTIZABLE (or
    explicitly excluded) stay fp32; dequantize happens in the kernel
    epilogue so adjacent fp32 ops see ordinary floats.
    """
    from ..symbol.symbol import SymNode, Symbol

    param_arrays = {k: (v.asnumpy() if hasattr(v, "asnumpy")
                        else onp.asarray(v)) for k, v in params.items()}
    # param-only subtrees (e.g. the s2d stem's weight re-expression)
    # collapse to plain params first so the folds below see conv-fed-by-
    # variable shapes; then conv+bn(+relu) -> one conv with folded weights
    # and a relu epilogue (reference: MKLDNN subgraph fuse + quantize pass)
    sym, param_arrays = _constant_fold(sym, param_arrays)
    sym, param_arrays = _fold_bn_relu(sym, param_arrays)
    new_params: Dict[str, onp.ndarray] = dict(param_arrays)
    cache: Dict[int, SymNode] = {}

    def rewrite(n) -> SymNode:
        got = cache.get(id(n))
        if got is not None:
            return got
        new_inputs = [(rewrite(src), i) for (src, i) in n.inputs]
        out = None
        # quantized_conv implements the 2D NCHW/NHWC paths (the
        # channel-minor fast path quantizes natively); other ranks /
        # layouts stay fp32 rather than silently mis-lowering
        conv_ok = (n.op != "Convolution"
                   or (len(n.attrs.get("kernel", ())) == 2
                       and n.attrs.get("layout") in (None, "NCHW", "NHWC")))
        if (n.op in QUANTIZABLE and conv_ok
                and n.name not in excluded_names
                and len(n.inputs) >= 2):
            data_src, data_idx = n.inputs[0]
            w_src, _wi = n.inputs[1]
            in_name = _out_name(data_src, data_idx)
            w_is_param = w_src.op is None and w_src.name in param_arrays
            rng = calib_ranges.get(in_name)
            if data_src.op is None:          # graph input: calibrated too?
                rng = rng or calib_ranges.get(data_src.name)
            if w_is_param and rng is not None:
                lo, hi = rng
                d_scale = _sym_scale(lo, hi)
                w = param_arrays[w_src.name]
                w_absmax = float(onp.abs(w).max()) or 1e-12
                w_scale = w_absmax / INT8_MAX
                qw = onp.clip(onp.round(w / w_scale), INT8_MIN,
                              INT8_MAX).astype(onp.int8)
                qw_name = w_src.name + "_quantized"
                new_params[qw_name] = qw
                qw_node = SymNode(None, qw_name, {}, [])
                # runtime activation quantize with the calibrated range
                qa = SymNode("quantize", n.name + "_qdata",
                             {"min_range": lo, "max_range": hi},
                             [new_inputs[0]])
                qop = ("quantized_conv" if n.op == "Convolution"
                       else "quantized_fully_connected")
                attrs = dict(n.attrs)
                attrs["data_scale"] = d_scale
                attrs["w_scale"] = w_scale
                q_inputs = [(qa, 0), (qw_node, 0)] + new_inputs[2:]
                out = SymNode(qop, n.name + "_quantized", attrs, q_inputs,
                              num_outputs=1)
        if out is None:
            out = SymNode(n.op, n.name, dict(n.attrs), new_inputs,
                          n.num_outputs)
        cache[id(n)] = out
        return out

    new_outputs = [(rewrite(n), i) for (n, i) in sym._outputs]
    new_sym = Symbol(new_outputs)
    _fuse_requantize(new_sym)
    # prune params the rewritten graph no longer references (a shared /
    # excluded consumer may still need the fp32 copy, so pruning is by
    # actual reference, not by what was quantized)
    referenced = {n.name for n in new_sym._topo() if n.op is None}
    new_params = {k: v for k, v in new_params.items() if k in referenced}
    return new_sym, new_params


class QuantizedNet:
    """Callable wrapper: jitted execution of a quantized symbol."""

    def __init__(self, sym, params: Dict[str, onp.ndarray]):
        from ..symbol.symbol import _jit_graph

        self.sym = sym
        self.params = {k: jnp.asarray(v) for k, v in params.items()}
        data_names = [a for a in sym.list_arguments() if a not in params]
        assert len(data_names) == 1, data_names
        self._data_name = data_names[0]
        self._fn = _jit_graph(sym)          # shared jit cache (symbol.py)

    def stage(self, device=None):
        """Commit the quantized params to ``device`` (default backend's
        device 0 when None).  Conversion/calibration usually runs under a
        host-CPU default device; without re-staging, every call would
        re-transfer the weights to the accelerator."""
        device = device or jax.devices()[0]
        self.params = {k: jax.device_put(v, device)
                       for k, v in self.params.items()}
        jax.block_until_ready(list(self.params.values()))
        return self

    def __call__(self, x):
        x = x._data if hasattr(x, "_data") else jnp.asarray(x)
        outs = self._fn({**self.params, self._data_name: x})
        return outs[0] if len(outs) == 1 else outs


def quantize_net(net, calib_data: List[Any], calib_mode: str = "naive",
                 quantized_dtype: str = "int8",
                 excluded_names: Tuple[str, ...] = ()) -> QuantizedNet:
    """End-to-end driver (reference contrib/quantization.py
    quantize_model): trace the hybridizable ``net``, calibrate on the
    given batches, rewrite the graph, return a jitted int8 predictor."""
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap
    from ..context import current_context

    first = calib_data[0]
    if not isinstance(first, NDArray):
        first = _wrap(jnp.asarray(first), current_context())
    net(first)                                  # ensure traced shapes
    sym = net._trace_symbol()
    params = {k: v.data() for k, v in net.collect_params().items()}
    data_names = [a for a in sym.list_arguments() if a not in params]
    assert len(data_names) == 1, f"single-input nets only: {data_names}"
    feeds = [{data_names[0]: (b._data if hasattr(b, "_data")
                              else jnp.asarray(b))} for b in calib_data]
    for f in feeds:
        for k, v in params.items():
            f[k] = v._data if hasattr(v, "_data") else jnp.asarray(v)
    ranges = collect_calib_ranges(sym, feeds, mode=calib_mode)
    # graph inputs get their own observed range
    for f in feeds:
        v = onp.asarray(f[data_names[0]], onp.float32)
        lo, hi = float(v.min()), float(v.max())
        if data_names[0] in ranges:
            plo, phi = ranges[data_names[0]]
            lo, hi = min(lo, plo), max(hi, phi)
        ranges[data_names[0]] = (lo, hi)
    qsym, qparams = quantize_symbol(sym, params, ranges,
                                    quantized_dtype=quantized_dtype,
                                    excluded_names=excluded_names)
    return QuantizedNet(qsym, qparams)


# ---------------------------------------------------------------------------
# quantized operator breadth (reference src/operator/quantization/*.cc):
# int8 flows through pooling/activation/shape ops unchanged (same scale),
# elementwise arithmetic accumulates in int32, batch_norm folds into the
# scale, embedding gathers int8 rows.  All registered under both the bare
# and the reference's _contrib_* names.
# ---------------------------------------------------------------------------

@register("quantize_v2", num_inputs=1, num_outputs=-1, differentiable=False,
          aliases=("_contrib_quantize_v2",))
def quantize_v2(data, min_calib_range=None, max_calib_range=None,
                out_type="int8"):
    """Calibrated-range quantize (reference quantize_v2.cc); without a
    calibrated range, the data min/max is used (the reference's runtime
    min/max path)."""
    if min_calib_range is None or max_calib_range is None:
        amax = jnp.maximum(jnp.max(jnp.abs(data)), 1e-12)
        scale = INT8_MAX / amax
        q = jnp.clip(jnp.round(data * scale), INT8_MIN, INT8_MAX).astype(
            jnp.int8)
        return (q, -amax, amax)
    lo, hi = float(min_calib_range), float(max_calib_range)
    scale = INT8_MAX / max(abs(lo), abs(hi), 1e-12)
    q = jnp.clip(jnp.round(data * scale), INT8_MIN, INT8_MAX).astype(
        jnp.int8)
    return (q, jnp.float32(lo), jnp.float32(hi))


@register("quantized_act", num_inputs=3, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_act",))
def quantized_act(qdata, min_range, max_range, act_type="relu"):
    """int8 activation (reference quantized_activation.cc): relu keeps the
    scale (max(0,x) in int domain)."""
    if act_type != "relu":
        raise NotImplementedError(
            f"quantized_act supports relu (got {act_type}); dequantize for "
            "other activations")
    return (jnp.maximum(qdata, 0), min_range, max_range)


@register("quantized_pooling", num_inputs=3, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_pooling",))
def quantized_pooling(qdata, min_range, max_range, kernel=(2, 2),
                      stride=None, pad=(0, 0), pool_type="max",
                      global_pool=False):
    """int8 pooling (reference quantized_pooling.cc): max-pool stays in
    int8; avg-pool accumulates in int32 then renormalizes."""
    n, c, h, w = qdata.shape
    if global_pool:
        kernel, stride, pad = (h, w), (1, 1), (0, 0)
    stride = stride or kernel
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    pads = ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1]))
    if pool_type == "max":
        out = jax.lax.reduce_window(qdata, jnp.int8(-128), jax.lax.max,
                                    window, strides, pads)
    else:
        acc = jax.lax.reduce_window(
            qdata.astype(jnp.int32), jnp.int32(0), jax.lax.add, window,
            strides, pads)
        out = jnp.clip(jnp.round(acc / (kernel[0] * kernel[1])),
                       INT8_MIN, INT8_MAX).astype(jnp.int8)
    return (out, min_range, max_range)


@register("quantized_flatten", num_inputs=3, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_flatten",))
def quantized_flatten(qdata, min_range, max_range):
    return (qdata.reshape(qdata.shape[0], -1), min_range, max_range)


@register("quantized_concat", num_inputs=-1, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_concat",))
def quantized_concat(arrays, num_args=0, dim=1):
    """Concat int8 inputs (reference quantized_concat.cc): inputs are
    rescaled to the widest input range so one output scale is exact.
    arrays = [q0..qn-1, min0, max0, min1, max1, ...]."""
    n = num_args or len(arrays) // 3
    qs = arrays[:n]
    mins = arrays[n::2][:n]
    maxs = arrays[n + 1::2][:n]
    amaxs = [jnp.maximum(jnp.abs(lo), jnp.abs(hi))
             for lo, hi in zip(mins, maxs)]
    out_amax = amaxs[0]
    for a in amaxs[1:]:
        out_amax = jnp.maximum(out_amax, a)
    scaled = [
        jnp.clip(jnp.round(q.astype(jnp.float32) * (a / out_amax)),
                 INT8_MIN, INT8_MAX).astype(jnp.int8)
        for q, a in zip(qs, amaxs)]
    return (jnp.concatenate(scaled, axis=dim), -out_amax, out_amax)


@register("quantized_elemwise_add", num_inputs=6, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_elemwise_add",))
def quantized_elemwise_add(qa, qb, a_min, a_max, b_min, b_max):
    """int8 + int8 -> int32 accumulator with fp32 scales folded (reference
    quantized_elemwise_add.cc); output re-quantized to the sum range."""
    sa = jnp.maximum(jnp.maximum(jnp.abs(a_min), jnp.abs(a_max)),
                     1e-12) / INT8_MAX
    sb = jnp.maximum(jnp.maximum(jnp.abs(b_min), jnp.abs(b_max)),
                     1e-12) / INT8_MAX
    f = qa.astype(jnp.float32) * sa + qb.astype(jnp.float32) * sb
    out_amax = jnp.maximum(jnp.abs(a_min) + jnp.abs(b_min),
                           jnp.abs(a_max) + jnp.abs(b_max))
    q = jnp.clip(jnp.round(f * (INT8_MAX / jnp.maximum(out_amax, 1e-12))),
                 INT8_MIN, INT8_MAX).astype(jnp.int8)
    return (q, -out_amax, out_amax)


@register("quantized_elemwise_mul", num_inputs=6, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_elemwise_mul",))
def quantized_elemwise_mul(qa, qb, a_min, a_max, b_min, b_max):
    """int8 * int8 -> int32 (exact); scales multiply (reference
    quantized_elemwise_mul.cc)."""
    acc = qa.astype(jnp.int32) * qb.astype(jnp.int32)
    sa = jnp.maximum(jnp.maximum(jnp.abs(a_min), jnp.abs(a_max)),
                     1e-12)
    sb = jnp.maximum(jnp.maximum(jnp.abs(b_min), jnp.abs(b_max)),
                     1e-12)
    out_amax = sa * sb
    return (acc, -out_amax, out_amax)


@register("quantized_batch_norm", num_inputs=7, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_batch_norm",))
def quantized_batch_norm(qdata, gamma, beta, moving_mean, moving_var,
                         min_range, max_range, eps=1e-3,
                         min_calib_range=None, max_calib_range=None):
    """Inference BN over int8 (reference quantized_batch_norm.cc): folds
    (gamma, beta, mean, var) into a per-channel affine applied in fp32,
    then re-quantizes to the calibrated output range."""
    in_scale = jnp.maximum(jnp.maximum(jnp.abs(min_range),
                                       jnp.abs(max_range)), 1e-12) / INT8_MAX
    inv = gamma / jnp.sqrt(moving_var + eps)
    shape = (1, -1) + (1,) * (qdata.ndim - 2)
    f = (qdata.astype(jnp.float32) * in_scale - moving_mean.reshape(shape)) \
        * inv.reshape(shape) + beta.reshape(shape)
    lo = float(min_calib_range if min_calib_range is not None else -1.0)
    hi = float(max_calib_range if max_calib_range is not None else 1.0)
    out_scale = INT8_MAX / max(abs(lo), abs(hi), 1e-12)
    q = jnp.clip(jnp.round(f * out_scale), INT8_MIN, INT8_MAX).astype(
        jnp.int8)
    return (q, jnp.float32(lo), jnp.float32(hi))


@register("quantized_embedding", num_inputs=4, num_outputs=-1,
          differentiable=False, aliases=("_contrib_quantized_embedding",))
def quantized_embedding(indices, qweight, min_range, max_range,
                        input_dim=0, output_dim=0):
    """Gather int8 rows (reference quantized_indexing_op.cc); the scale is
    unchanged by a gather."""
    out = jnp.take(qweight, indices.astype(jnp.int32), axis=0)
    return (out, min_range, max_range)


@register("calibrate_entropy", num_inputs=1, num_outputs=-1,
          differentiable=False, aliases=("_contrib_calibrate_entropy",))
def calibrate_entropy(hist_and_edges, num_quantized_bins=255):
    """KL-divergence threshold selection over a histogram (reference
    calibrate.cc): picks the clip threshold whose quantized distribution
    minimizes KL against the clipped reference distribution.  Host-side
    (calibration is offline); input = histogram counts, attr-free edges
    assumed symmetric uniform."""
    import numpy as _onp

    hist = _onp.asarray(hist_and_edges, dtype=_onp.float64)
    nbins = hist.size
    best_kl, best_t = _onp.inf, nbins
    for t in range(num_quantized_bins, nbins + 1, 2):
        p = hist[:t].copy()
        p[t - 1] += hist[t:].sum()          # clip mass into the last bin
        p_sum = p.sum()
        if p_sum == 0:
            continue
        # quantize t bins down to num_quantized_bins, then expand back
        factor = t / num_quantized_bins
        q = _onp.zeros(t)
        for j in range(num_quantized_bins):
            lo = int(_onp.floor(j * factor))
            hi = int(_onp.ceil((j + 1) * factor))
            mass = hist[lo:hi].sum()
            nz = (hist[lo:hi] > 0).sum()
            if nz:
                q[lo:hi] = _onp.where(hist[lo:hi] > 0, mass / nz, 0)
        q_sum = q.sum()
        if q_sum == 0:
            continue
        pn, qn = p / p_sum, q / q_sum
        mask = (pn > 0) & (qn > 0)
        kl = float(_onp.sum(pn[mask] * _onp.log(pn[mask] / qn[mask])))
        if kl < best_kl:
            best_kl, best_t = kl, t
    return (jnp.asarray(best_t, jnp.int32), jnp.asarray(best_kl))


# ---------------------------------------------------------------------------
# intgemm family (reference src/operator/contrib/intgemm/*.cc): CPU int8
# GEMM pre/post-processing ops.  On TPU the MXU consumes plain int8 tiles,
# so prepare_* are layout no-ops with the same contracts.
# ---------------------------------------------------------------------------

@register("intgemm_maxabsolute", num_inputs=1, differentiable=False,
          aliases=("_contrib_intgemm_maxabsolute",))
def intgemm_maxabsolute(data):
    return jnp.max(jnp.abs(data))


@register("intgemm_prepare_data", num_inputs=2, differentiable=False,
          aliases=("_contrib_intgemm_prepare_data",))
def intgemm_prepare_data(data, maxabs):
    """fp32 -> int8 with scale 127/maxabs (reference
    intgemm/prepare_data_op.cc)."""
    scale = INT8_MAX / jnp.maximum(maxabs, 1e-12)
    return jnp.clip(jnp.round(data * scale), INT8_MIN, INT8_MAX).astype(
        jnp.int8)


@register("intgemm_prepare_weight", num_inputs=-1, differentiable=False,
          aliases=("_contrib_intgemm_prepare_weight",))
def intgemm_prepare_weight(arrays, already_quantized=False):
    """Weight pre-pass (reference intgemm/prepare_weight_op.cc).  The
    reference permutes into a CPU-register tiled layout; the MXU needs no
    relayout, so this quantizes (if needed) and keeps row-major."""
    if already_quantized or len(arrays) == 1:
        return arrays[0].astype(jnp.int8)
    data, maxabs = arrays
    scale = INT8_MAX / jnp.maximum(maxabs, 1e-12)
    return jnp.clip(jnp.round(data * scale), INT8_MIN, INT8_MAX).astype(
        jnp.int8)


@register("intgemm_take_weight", num_inputs=2, differentiable=False,
          aliases=("_contrib_intgemm_take_weight",))
def intgemm_take_weight(qweight, indices):
    """Gather rows of a prepared weight (reference
    intgemm/take_weight_op.cc — vocabulary shortlisting)."""
    return jnp.take(qweight, indices.astype(jnp.int32), axis=0)


@register("intgemm_fully_connected", num_inputs=-1, differentiable=False,
          aliases=("_contrib_intgemm_fully_connected",))
def intgemm_fully_connected(arrays, num_hidden=0, no_bias=True, flatten=True,
                            out_type="float32"):
    """int8 x int8 -> int32/fp32 GEMM (reference
    intgemm/intgemm_fully_connected_op.cc).  arrays = [data_s8, weight_s8,
    scale (fp32 scalar = product of the two quantization scales), (bias)]."""
    qd, qw = arrays[0], arrays[1]
    if flatten and qd.ndim > 2:
        qd = qd.reshape(qd.shape[0], -1)
    acc = jax.lax.dot_general(
        qd.astype(jnp.int8), qw.astype(jnp.int8),
        (((qd.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    if out_type == "int32":
        return acc
    scale = arrays[2] if len(arrays) > 2 else jnp.float32(1)
    out = acc.astype(jnp.float32) * scale
    if not no_bias and len(arrays) > 3:
        out = out + arrays[3]
    return out
