"""Selector-based subgraph partitioner (round-2 VERDICT item 4).

Reference analog: src/operator/subgraph/subgraph_property.h:86-252 (seed +
BFS grow + filter selector protocol) and build_subgraph.cc.  The done bar:
a backend rewrites exactly the conv+bn+relu subgraphs of resnet18 —
verified by node-count diff and output equality — while the rest of the
graph is untouched.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.symbol.subgraph import (ConvBNReLUProperty, OpChainSelector,
                                       SubgraphProperty, SubgraphSelector,
                                       partition)


def _trace(net, x):
    net(x)
    sym = net._trace_symbol()
    params = {k: v.data() for k, v in net.collect_params().items()}
    return sym, params


def _opcount(sym):
    from collections import Counter

    return Counter(n.op for n in sym._topo() if n.op)


def _eval(sym, params, x):
    feed = {"data": x._data if hasattr(x, "_data") else x}
    for k, v in params.items():
        feed[k] = v._data if hasattr(v, "_data") else onp.asarray(v)
    out = sym.eval(**{k: nd.array(onp.asarray(v)) for k, v in feed.items()})
    return onp.asarray((out[0] if isinstance(out, list) else out).asnumpy())


def test_resnet18_conv_bn_relu_partition():
    rng = onp.random.RandomState(0)
    net = vision.get_model("resnet18_v1", classes=10)
    net.initialize(mx.init.Xavier())
    x = nd.array(rng.rand(2, 3, 32, 32).astype(onp.float32))
    sym, params = _trace(net, x)
    before = _opcount(sym)

    new_sym, new_params = sym.optimize_for(ConvBNReLUProperty(), params)
    after = _opcount(new_sym)

    # every BatchNorm sat directly on a conv output in resnet18_v1, so all
    # fold away; relus NOT adjacent to a conv+bn chain (post-residual-add)
    # survive — the partitioner touched ONLY the matched subgraphs
    assert after.get("BatchNorm", 0) == 0, after
    assert before["BatchNorm"] > 0
    assert after["Convolution"] == before["Convolution"]
    fused = [n for n in new_sym._topo()
             if n.op == "Convolution" and n.attrs.get("fused_relu")]
    assert len(fused) > 0
    # untouched op population is preserved exactly
    for op in ("broadcast_add", "elemwise_add", "Pooling", "Flatten",
               "FullyConnected"):
        assert after.get(op, 0) == before.get(op, 0), op
    # node-count diff: removed = #BN + #folded relus
    removed = sum(before.values()) - sum(after.values())
    assert removed == before["BatchNorm"] + len(fused)

    ref = _eval(sym, params, x)
    got = _eval(new_sym, new_params, x)
    onp.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_partition_leaves_unmatched_graph_identical():
    rng = onp.random.RandomState(1)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"),
            nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    x = nd.array(rng.rand(4, 4).astype(onp.float32))
    sym, params = _trace(net, x)
    new_sym, _ = partition(sym, ConvBNReLUProperty(), params)
    assert _opcount(new_sym) == _opcount(sym)
    onp.testing.assert_allclose(_eval(new_sym, params, x),
                                _eval(sym, params, x), rtol=1e-6)


def test_custom_property_and_convexity_guard():
    """A user-defined property over the selector protocol; the partitioner
    must refuse a non-convex match (an external node on a path between two
    members) by shrinking the group instead of building a cyclic graph."""

    class SquareChain(SubgraphProperty):
        name = "SQ"

        def create_selector(self):
            return OpChainSelector(("square", "square"))

        def create_subgraph_node(self, sub_sym, subgraph_id, params):
            from mxnet_tpu.symbol.symbol import Symbol

            order = [n for n in sub_sym._topo() if n.op]
            if len(order) != 2:
                return None          # shrunk by convexity repair: decline
            data = Symbol([order[0].inputs[0]])   # the input placeholder
            return data ** 4                      # x^4 in one node

    import mxnet_tpu.symbol as S

    x = S.var("x")
    # convex case: square -> square fuses
    y = S.square(S.square(x))
    new_sym, _ = partition(y, SquareChain(), {})
    ops = [n.op for n in new_sym._topo() if n.op]
    assert "square" not in ops
    v = new_sym.eval(x=nd.array(onp.array([2.0], onp.float32)))
    v = v[0] if isinstance(v, list) else v
    assert float(v.asnumpy().ravel()[0]) == 16.0

    # NON-convex: square -> (external sqrt) -> square; fusing both squares
    # would cycle through sqrt.  The group must shrink (then decline).
    a = S.square(x)
    b = S.sqrt(a)
    c = S.square(b)
    out = c
    new_sym2, _ = partition(out, SquareChain(), {})
    ops2 = sorted(n.op for n in new_sym2._topo() if n.op)
    assert ops2 == ["sqrt", "square", "square"]
    v1 = out.eval(x=nd.array(onp.array([3.0], onp.float32)))
    v2 = new_sym2.eval(x=nd.array(onp.array([3.0], onp.float32)))
    v1 = (v1[0] if isinstance(v1, list) else v1).asnumpy()
    v2 = (v2[0] if isinstance(v2, list) else v2).asnumpy()
    onp.testing.assert_allclose(v1, v2)


def test_register_backend_accepts_property():
    from mxnet_tpu import library

    name = "TEST_SG_PROP"
    if name not in library.list_backends():
        library.register_backend(name, ConvBNReLUProperty())
    prop = library.get_backend(name)
    assert isinstance(prop, SubgraphProperty)

    rng = onp.random.RandomState(2)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1, in_channels=3, use_bias=False),
            nn.BatchNorm(in_channels=4), nn.Activation("relu"))
    net.initialize(mx.init.Xavier())
    x = nd.array(rng.rand(1, 3, 8, 8).astype(onp.float32))
    sym, params = _trace(net, x)
    new_sym, new_params = sym.optimize_for(name, params)
    ops = [n.op for n in new_sym._topo() if n.op]
    assert ops == ["Convolution"]
    onp.testing.assert_allclose(_eval(new_sym, new_params, x),
                                _eval(sym, params, x), rtol=2e-4, atol=2e-4)


def test_weightless_conv_declines_instead_of_crashing():
    """A Convolution node built without an explicit weight variable (this
    frontend does not auto-create weight vars) must make the property
    DECLINE the match, not crash optimize_for with IndexError."""
    x = mx.sym.Variable("data")
    c = mx.sym.Convolution(data=x, num_filter=4, kernel=(3, 3), pad=(1, 1),
                           no_bias=True)
    g, b = mx.sym.Variable("g"), mx.sym.Variable("b")
    m, v = mx.sym.Variable("m"), mx.sym.Variable("v")
    bn = mx.sym.BatchNorm(data=c, gamma=g, beta=b, moving_mean=m,
                          moving_var=v)
    r = mx.sym.relu(bn)
    params = {k: onp.ones(4, onp.float32) for k in ("g", "b", "m", "v")}
    new_sym, _ = r.optimize_for(ConvBNReLUProperty(), params)
    # nothing fused: the original op sequence survives
    ops = _opcount(new_sym)
    assert ops.get("BatchNorm", 0) == 1
