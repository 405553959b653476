"""The dense product's weight gradient from materialised operands
(``ops/nn.py``: ``fully_connected``, ``dense_product``): where the rule
engages, by the widths alone, the gradients are the plain product's to the
bit, the barrier stands exactly in front of the weight-gradient product,
the two counters count each operand site once a trace, and a compiled step
neither retraces nor dispatches more.  CPU only: values and counts.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, cached_step, gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import nn as ops_nn

BF16 = jnp.bfloat16

# which operands the weight gradient reads as written arrays
SIDES = {"neither": (False, False), "activation": (True, False),
         "gradient": (False, True), "both": (True, True)}


def _barriers(jaxpr):
    """The operand shapes of every ``optimization_barrier`` in ``jaxpr``
    and in the jaxprs nested in it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "optimization_barrier":
            found.extend(tuple(v.aval.shape) for v in eqn.invars)
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                found.extend(_barriers(inner))
    return found


def _inputs(lead, num_hidden, in_units, seed=0):
    rng = onp.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*lead, in_units), jnp.float32)
    w = jnp.asarray(rng.randn(num_hidden, in_units) * 0.3, jnp.float32)
    b = jnp.asarray(rng.randn(num_hidden), jnp.float32)
    dy = jnp.asarray(rng.randn(*lead, num_hidden), BF16)
    return x, w, b, dy


@pytest.mark.parametrize("lead", [(6,), (2, 5)], ids=["2d", "bsc"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("engaged", list(SIDES))
def test_gradients_equal_the_plain_products_bit_for_bit(
        lead, bias, engaged, monkeypatch):
    """Under bf16 AMP (every input cast inside the operator, as the policy
    casts them): the data's, the weight's and the bias's gradients equal
    jax's own ``vjp`` of ``x @ W.T + b`` exactly, whichever operands are
    written."""
    monkeypatch.setattr(ops_nn, "_wgrad_sides", lambda shape: SIDES[engaged])
    num_hidden, in_units = 24, 16
    x, w, b, dy = _inputs(lead, num_hidden, in_units)

    def op(x, w, b):
        arrays = [x.astype(BF16), w.astype(BF16)]
        if bias:
            arrays.append(b.astype(BF16))
        return ops_nn.fully_connected(arrays, num_hidden=num_hidden,
                                      no_bias=not bias, flatten=False)

    def plain(x, w, b):
        out = jnp.matmul(x.astype(BF16), w.astype(BF16).T)
        return out + b.astype(BF16) if bias else out

    got_y, got_vjp = jax.vjp(op, x, w, b)
    want_y, want_vjp = jax.vjp(plain, x, w, b)
    assert got_y.dtype == BF16
    onp.testing.assert_array_equal(onp.asarray(got_y, onp.float32),
                                   onp.asarray(want_y, onp.float32))
    for name, got, want in zip("xwb", got_vjp(dy), want_vjp(dy)):
        assert got.dtype == want.dtype, name
        onp.testing.assert_array_equal(onp.asarray(got), onp.asarray(want),
                                       err_msg=name)


@pytest.mark.parametrize("engaged", list(SIDES))
def test_the_barrier_stands_where_the_rule_engages(engaged, monkeypatch):
    """The differentiated program holds one barrier per written operand,
    over the activation (tokens, in_units) or the output's gradient
    (tokens, num_hidden); the forward program holds none."""
    monkeypatch.setattr(ops_nn, "_wgrad_sides", lambda shape: SIDES[engaged])
    num_hidden, in_units = 24, 16
    x, w, _, _ = _inputs((2, 5), num_hidden, in_units)

    def loss(x, w):
        y = ops_nn.fully_connected([x.astype(BF16), w.astype(BF16)],
                                   num_hidden=num_hidden, no_bias=True,
                                   flatten=False)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    want = {"neither": [], "activation": [(2, 5, in_units)],
            "gradient": [(2, 5, num_hidden)],
            "both": [(2, 5, in_units), (2, 5, num_hidden)]}[engaged]
    grads = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr
    assert sorted(_barriers(grads)) == sorted(want)
    assert _barriers(jax.make_jaxpr(loss)(x, w).jaxpr) == []


def _sites(num_hidden, in_units):
    """(operands written, operands fused) as a trace of one product with a
    weight of this shape counts them (no array is made)."""
    base = telemetry.snapshot()
    jax.eval_shape(
        lambda x, w: ops_nn.fully_connected([x, w], num_hidden=num_hidden,
                                            no_bias=True, flatten=False),
        jax.ShapeDtypeStruct((1, 8192, in_units), BF16),
        jax.ShapeDtypeStruct((num_hidden, in_units), BF16))
    moved = telemetry.delta(base)
    return (moved.get("dense.wgrad_materialised", 0),
            moved.get("dense.wgrad_fused", 0))


def test_the_rule_reads_the_widths_alone():
    """Both operands are written where the product spans at least
    ``WGRAD_MIN_TILES`` output tiles of ``WGRAD_TILE`` columns on each
    side, neither elsewhere; each operand counts once on one counter."""
    edge = (ops_nn.WGRAD_MIN_TILES - 1) * ops_nn.WGRAD_TILE
    assert edge == 2688
    for shape, written in [((22016, 3840), True), ((3840, 11008), True),
                           ((2880, 3840), True), ((edge + 1, edge + 1), True),
                           ((edge, 10 ** 5), False), ((10 ** 5, edge), False),
                           ((16384, 2048), False), ((30528, 768), False),
                           ((1000, 2048), False), ((1440, 3840), False)]:
        assert ops_nn._wgrad_sides(shape) == (written, written), shape
        assert _sites(*shape) == ((2, 0) if written else (0, 2)), shape


# the product sites of each configuration's step that write their operands
WRITTEN = {"olmo_hybrid_7b_tp2": {"gate_up_proj": 4, "down_proj": 4,
                                  "o_proj": 3, "v_proj": 3, "g_proj": 3,
                                  "lm_head": 1}}


def test_which_sites_of_the_cells_engage(monkeypatch):
    """Every benchmark configuration built at its own widths (nothing
    allocated): the delta-rule cell writes the operands of its
    feed-forward's two products, of the delta-rule mixers' ``o_proj``,
    ``v_proj`` and ``g_proj`` and of its head; no other cell writes any."""
    from perfbench import manifest

    monkeypatch.setattr(gluon.Block, "initialize",
                        lambda self, *a, **k: None)
    configs = {}
    for workload in manifest.load_benchmark()["workloads"]:
        cell = manifest.resolve(workload["name"])
        configs.setdefault(cell.config, cell)
    assert len(configs) == 6
    for name, cell in configs.items():
        net = cell.config_module.build(mx, cell.sizes)["net"]
        written = {}
        for key, block in _dense_blocks(net):
            if ops_nn._wgrad_sides(block.weight.shape)[0]:
                written[key] = written.get(key, 0) + 1
        assert written == WRITTEN.get(name, {}), name


def _dense_blocks(block):
    """(key it is registered under, block) of every ``Dense`` in ``block``."""
    for key, child in block._children.items():
        if isinstance(child, nn.Dense):
            yield key, child
        yield from _dense_blocks(child)


class _Net(gluon.HybridBlock):
    """Two dense layers: at a tile of 2 columns the first spans 12 and 8
    tiles and writes its operands, the second (4 wide) does not."""

    def __init__(self):
        super().__init__()
        self.up = nn.Dense(24, in_units=16, flatten=False,
                           activation="relu")
        self.down = nn.Dense(4, in_units=24, flatten=False)

    def forward(self, x):
        return self.down(self.up(x))


def _trained(tile, monkeypatch, steps=4):
    monkeypatch.setattr(ops_nn, "WGRAD_TILE", tile)
    net = _Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(3)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.2)._data)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    l2 = gluon.loss.L2Loss()
    step = trainer.compile_step(net, lambda n, x, y: l2(n(x), y))
    x = mx.nd.array(rng.randn(3, 5, 16))
    y = mx.nd.array(rng.randn(3, 5, 4))
    base = telemetry.snapshot()
    t0, d0 = cached_step.trace_count(), cached_step.dispatch_count()
    step(x, y, batch_size=3)
    first = telemetry.delta(base)
    counts = [(first.get("dense.wgrad_materialised", 0),
               first.get("dense.wgrad_fused", 0))]
    traces = [cached_step.trace_count() - t0]
    base = telemetry.snapshot()
    t1, d1 = cached_step.trace_count(), cached_step.dispatch_count()
    for _ in range(steps - 1):
        step(x, y, batch_size=3)
    later = telemetry.delta(base)
    counts.append((later.get("dense.wgrad_materialised", 0),
                   later.get("dense.wgrad_fused", 0)))
    traces.append(cached_step.trace_count() - t1)
    dispatches = (d1 - d0, cached_step.dispatch_count() - d1)
    params = {k: p.data().asnumpy()
              for k, p in sorted(net.collect_params().items())}
    return counts, traces, dispatches, params


@pytest.mark.parametrize("low", [None, "bfloat16"], ids=["fp32", "amp_bf16"])
def test_a_compiled_step_counts_each_site_once_and_learns_the_same(
        low, monkeypatch):
    """Engaged (tiles of 2 columns: two operands written, two fused) and
    not, in float32 and under ``amp.init``: one trace and one dispatch a
    step either way, each operand site counted once in that trace and never
    again, and the same weights after four steps of Adam, to the bit."""
    if low is not None:
        amp.init(low)
    try:
        on = _trained(2, monkeypatch)
        off = _trained(384, monkeypatch)
    finally:
        amp.uninit()
    assert on[0] == [(2, 2), (0, 0)]
    assert off[0] == [(0, 4), (0, 0)]
    for run in (on, off):
        assert run[1] == [1, 0]
        assert run[2] == (1, 3)
    assert on[3].keys() == off[3].keys()
    for name in on[3]:
        onp.testing.assert_array_equal(on[3][name], off[3][name],
                                       err_msg=name)
