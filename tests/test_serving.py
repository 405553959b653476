"""Shape-bucketed compiled inference + dynamic micro-batching (PR 4
tentpole, ``mxnet_tpu/serving.py``) and the shared bucket policy in
``gluon/block.py`` / ``cached_step.py``.

Covers the acceptance contract: (1) padded-vs-unpadded bit-exact parity
over a randomized variable-length stream with 0 steady-state retraces
and program count <= bucket count, (2) explicit REFUSAL for models whose
outputs couple across a padded axis (mean-style length reductions) with
still-correct results, (3) bucket-selection edges (exact fit, one-over,
above-largest-bucket fallback), (4) micro-batcher coalescing and the
max-delay flush, (5) the ``serving.infer`` fault site (injected timeout
-> single-request fallback, never a dropped request), (6) the DataLoader
``last_batch='pad'`` tail contract, (7) train-step bucketing (pad-safe
masked loss bit-exact vs unpadded eager; non-pad-safe loss refused), and
(8) the extended tools/check_dispatch_budget.py CI gate.
"""
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import cached_step, faults, gluon, serving
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0, hybridize=False):
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8, activation="relu")
            self.d2 = nn.Dense(4, in_units=16)

        def forward(self, x):
            return self.d2(self.d1(x))

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    if hybridize:
        net.hybridize()
    return net


# ---------------------------------------------------------------------------
# BucketPolicy
# ---------------------------------------------------------------------------
def test_bucket_policy_pow2():
    p = serving.BucketPolicy("pow2")
    assert [p.bucket(n) for n in (1, 2, 3, 5, 8, 9, 33)] == \
        [1, 2, 4, 8, 8, 16, 64]
    assert p.enabled


def test_bucket_policy_explicit_grid_and_edges():
    p = serving.BucketPolicy("8,4,16")          # unsorted input is fine
    assert p.buckets() == (4, 8, 16)
    assert p.bucket(4) == 4                      # exact fit
    assert p.bucket(5) == 8                      # one-over -> next bucket
    assert p.bucket(16) == 16
    assert p.bucket(17) is None                  # above largest -> exact


def test_bucket_policy_none_and_invalid():
    assert not serving.BucketPolicy("none").enabled
    with pytest.raises(ValueError):
        serving.BucketPolicy("8,banana")
    with pytest.raises(ValueError):
        serving.BucketPolicy("0,8")


# ---------------------------------------------------------------------------
# padded-vs-unpadded parity over a variable-length stream
# ---------------------------------------------------------------------------
def test_serving_padded_parity_bounded_programs():
    net = _mlp(0)
    rng = onp.random.RandomState(42)
    with serving.ServingEngine(net, max_delay_us=200) as eng:
        # warm the buckets the stream can hit
        for b in (1, 2, 4, 8):
            eng.infer(mx.nd.array(rng.randn(b, 8)))
        t0, d0 = serving.trace_count(), serving.dispatch_count()
        # lengths >= 2: n=1 hits XLA's matvec special case whose compiled
        # program differs from eager by one ulp INDEPENDENT of padding
        # (same compiled-vs-eager property as hybridize); the padding
        # contract itself is what this test pins down
        lengths = rng.randint(2, 9, size=20)
        for n in lengths:
            x = mx.nd.array(rng.randn(int(n), 8))
            out = eng.infer(x)
            with mx.autograd.pause():
                ref = net.forward(x)
            assert out.shape == (int(n), 4)
            assert onp.array_equal(out.asnumpy(), ref.asnumpy()), n
        # steady state: 0 retraces, one launch per request (sequential),
        # program count bounded by the bucket grid
        assert serving.trace_count() - t0 == 0
        assert serving.dispatch_count() - d0 == len(lengths)
        assert len(eng._programs) <= 4
        assert eng.bucket_refused is None
        assert eng.stats()["verify_runs"] >= 1    # padding WAS verified


def test_serving_numpy_request_staged_not_baked():
    """A numpy payload must be staged to device (DataLoader._wrap
    contract), not traced as a constant: two different numpy requests of
    the same shape must NOT build two programs."""
    net = _mlp(1)
    rng = onp.random.RandomState(0)
    with serving.ServingEngine(net, max_delay_us=200) as eng:
        a = rng.randn(4, 8).astype(onp.float32)
        b = rng.randn(4, 8).astype(onp.float32)
        out_a = eng.infer(a)
        t0 = serving.trace_count()
        out_b = eng.infer(b)
        assert serving.trace_count() == t0          # same program
        assert not onp.array_equal(out_a.asnumpy(), out_b.asnumpy())
        with mx.autograd.pause():
            ref = net.forward(mx.nd.array(b))
        assert onp.array_equal(out_b.asnumpy(), ref.asnumpy())


# ---------------------------------------------------------------------------
# refusal: outputs that couple across the padded axis
# ---------------------------------------------------------------------------
def test_serving_mean_over_length_refused_but_correct():
    """A reduction-over-length model: once the length axis goes dynamic
    and padding kicks in, the first padded dispatch is verified, fails
    bit-exactness, and bucketing is REFUSED explicitly — every result
    (including the one that triggered the refusal) stays correct."""

    class MeanLen(gluon.HybridBlock):
        def forward(self, x):
            return x.mean(axis=1)       # padded zeros shift the mean

    net = MeanLen()
    rng = onp.random.RandomState(3)
    with serving.ServingEngine(net, max_delay_us=200) as eng:
        for L in (5, 6, 9, 3):
            x = mx.nd.array(rng.randn(2, L))
            out = eng.infer(x)
            with mx.autograd.pause():
                ref = net.forward(x)
            assert onp.array_equal(out.asnumpy(), ref.asnumpy()), L
        assert eng.bucket_refused is not None
        assert "bit-exact" in eng.bucket_refused
        # the refusal is logged through the faults event log
        evs = faults.events("serving.infer")
        assert any(e["action"] == "bucket_refused" for e in evs)


def test_serving_above_largest_bucket_falls_back_exact():
    os.environ["MXNET_SHAPE_BUCKETS"] = "4,8"
    try:
        net = _mlp(2)
        rng = onp.random.RandomState(1)
        with serving.ServingEngine(net, max_delay_us=200) as eng:
            out = eng.infer(mx.nd.array(rng.randn(12, 8)))   # > largest
            assert out.shape == (12, 4)
            assert eng.stats()["bucket_fallbacks"] == 1
            # exact fit: no pad rows recorded beyond the true rows
            eng.infer(mx.nd.array(rng.randn(4, 8)))
            s = eng.stats()
            assert s["padded_rows"] - s["true_rows"] == 0
            # one-over: 5 rows pad to the 8 bucket
            eng.infer(mx.nd.array(rng.randn(5, 8)))
            s = eng.stats()
            assert s["padded_rows"] - s["true_rows"] == 3
    finally:
        os.environ.pop("MXNET_SHAPE_BUCKETS", None)


# ---------------------------------------------------------------------------
# micro-batching
# ---------------------------------------------------------------------------
def test_serving_coalesces_concurrent_requests():
    net = _mlp(4)
    rng = onp.random.RandomState(5)
    with serving.ServingEngine(net, max_batch=32,
                               max_delay_us=300_000) as eng:
        eng.infer(mx.nd.array(rng.randn(8, 8)))      # warm the 8 bucket
        xs = [mx.nd.array(rng.randn(2, 8)) for _ in range(4)]
        outs: dict = {}
        errs: list = []
        b0 = eng.stats()["batches"]

        def fire(i):
            try:
                outs[i] = eng.infer(xs[i])
            except BaseException as e:   # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs
        s = eng.stats()
        # 4 concurrent 2-row requests coalesce into at most 2 dispatches
        # (8 rows fit one bucket-8 batch; timing may split one off)
        assert s["batches"] - b0 <= 2
        assert s["coalesced"] >= 2
        for i, x in enumerate(xs):
            with mx.autograd.pause():
                ref = net.forward(x)
            assert onp.array_equal(outs[i].asnumpy(), ref.asnumpy()), i


def test_serving_max_delay_flushes_partial_batch():
    """A lone request must dispatch after ~max_delay even though
    max_batch is far from full."""
    net = _mlp(5)
    rng = onp.random.RandomState(6)
    with serving.ServingEngine(net, max_batch=32,
                               max_delay_us=10_000) as eng:
        eng.infer(mx.nd.array(rng.randn(2, 8)))      # warm (compiles)
        t0 = time.monotonic()
        out = eng.infer(mx.nd.array(rng.randn(2, 8)))
        elapsed = time.monotonic() - t0
        assert out.shape == (2, 4)
        assert elapsed < 5.0                          # not stuck at max_batch


# ---------------------------------------------------------------------------
# fault site: serving.infer
# ---------------------------------------------------------------------------
def test_serving_infer_fault_falls_back_single_request():
    """An injected timeout on the batched dispatch falls back to
    single-request processing — the request is answered, never dropped,
    and the recovery is visible in the event log."""
    net = _mlp(6)
    rng = onp.random.RandomState(7)
    with serving.ServingEngine(net, max_delay_us=200) as eng:
        x = mx.nd.array(rng.randn(3, 8))
        with faults.active(faults.FaultPlan().fail(
                "serving.infer", times=1, exc=TimeoutError)):
            out = eng.infer(x)
        with mx.autograd.pause():
            ref = net.forward(x)
        assert onp.array_equal(out.asnumpy(), ref.asnumpy())
        assert eng.stats()["single_fallbacks"] == 1
        evs = faults.events("serving.infer")
        assert any(e["action"] == "fallback" for e in evs)
        # the spent plan serves compiled again
        out2 = eng.infer(x)
        assert onp.array_equal(out2.asnumpy(), ref.asnumpy())


def test_serving_request_error_delivered_not_dropped():
    """A request the model itself rejects gets ITS error raised from
    infer() — the engine never wedges or drops it."""

    class Picky(gluon.HybridBlock):
        def forward(self, x):
            if x.shape[1] != 8:
                raise ValueError("bad width")
            return x * 2.0

    with serving.ServingEngine(Picky(), max_delay_us=200) as eng:
        with pytest.raises(ValueError, match="bad width"):
            eng.infer(mx.nd.array(onp.zeros((2, 3), onp.float32)))
        # engine still serves afterwards
        out = eng.infer(mx.nd.array(onp.ones((2, 8), onp.float32)))
        assert onp.array_equal(out.asnumpy(),
                               onp.full((2, 8), 2.0, onp.float32))


# ---------------------------------------------------------------------------
# hybridize(bucket=True): the block-level policy
# ---------------------------------------------------------------------------
def test_hybridize_bucket_parity_and_bounded_cache():
    net = _mlp(8)
    net.hybridize(bucket=True)
    rng = onp.random.RandomState(9)
    for n in (3, 5, 6, 7, 8):
        x = mx.nd.array(rng.randn(n, 8))
        out = net(x)
        with mx.autograd.pause():
            ref = net.forward(x)
        assert out.shape == (n, 4)
        assert onp.array_equal(out.asnumpy(), ref.asnumpy()), n
    assert net._bucket_refused is None


def test_hybridize_bucket_refuses_batch_coupled_model():
    class BatchMean(gluon.HybridBlock):
        def forward(self, x):
            return x - x.mean(axis=0, keepdims=True)   # couples rows

    net = BatchMean()
    net.hybridize(bucket=True)
    rng = onp.random.RandomState(10)
    x = mx.nd.array(rng.randn(5, 8))       # 5 -> pad to 8: verify fails
    out = net(x)
    ref = x.asnumpy() - x.asnumpy().mean(axis=0, keepdims=True)
    onp.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-6)
    assert net._bucket_refused is not None


def test_forward_cache_lru_cap():
    os.environ["MXNET_FORWARD_CACHE"] = "2"
    try:
        class Scaled(gluon.HybridBlock):
            def __init__(self):
                super().__init__()
                self.d = nn.Dense(4, in_units=8)

            def forward(self, x, k):
                return self.d(x) * k

        net = Scaled()
        net.initialize(mx.init.Xavier())
        net.hybridize()
        x = mx.nd.array(onp.ones((2, 8), onp.float32))
        for k in (1.0, 2.0, 3.0, 4.0):     # consts -> distinct signatures
            net(x, k)
        assert len(net._cached) <= 2
    finally:
        os.environ.pop("MXNET_FORWARD_CACHE", None)


# ---------------------------------------------------------------------------
# DataLoader last_batch='pad'
# ---------------------------------------------------------------------------
def test_dataloader_pad_mode_shapes_and_valid_counts():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset

    X = onp.arange(10, dtype=onp.float32).reshape(10, 1)
    ds = ArrayDataset(X, X[:, 0])
    dl = DataLoader(ds, batch_size=4, last_batch="pad")
    assert len(dl) == 3
    shapes, valids, tail = [], [], None
    for xb, _yb in dl:
        shapes.append(tuple(xb.shape))
        valids.append(dl.last_batch_valid)
        tail = xb.asnumpy()
    assert shapes == [(4, 1)] * 3
    assert valids == [4, 4, 2]
    # pad rows cycle the partial batch's own samples (deterministic)
    assert onp.array_equal(tail.ravel(), [8, 9, 8, 9])


def test_dataloader_pad_mode_workers():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset

    X = onp.arange(10, dtype=onp.float32).reshape(10, 1)
    ds = ArrayDataset(X, X[:, 0])
    dl = DataLoader(ds, batch_size=4, last_batch="pad", num_workers=2,
                    thread_pool=True)
    got = [(tuple(xb.shape), dl.last_batch_valid) for xb, _yb in dl]
    assert got == [((4, 1), 4), ((4, 1), 4), ((4, 1), 2)]


def test_dataloader_pad_rejects_batch_sampler():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    from mxnet_tpu.gluon.data.sampler import (BatchSampler,
                                              SequentialSampler)

    ds = ArrayDataset(onp.zeros((10, 1), onp.float32),
                      onp.zeros((10,), onp.float32))
    bs = BatchSampler(SequentialSampler(10), 4, "keep")
    with pytest.raises(ValueError):
        DataLoader(ds, batch_sampler=bs, last_batch="pad")


def test_pad_mode_keeps_compiled_step_at_one_trace():
    """The point of the satellite: with last_batch='pad' every batch of
    the epoch has the same shape, so the compiled train step never pays
    the tail retrace — one trace per epoch, bit-exact masked training."""
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset

    rng = onp.random.RandomState(11)
    X = rng.randn(10, 8).astype(onp.float32)
    Y = rng.randn(10, 4).astype(onp.float32)
    ds = ArrayDataset(X, Y)
    net = _mlp(12, hybridize=True)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})

    def masked_loss(n_, x, y, m):
        return (((n_(x) - y) ** 2) * m).sum()

    step = trainer.compile_step(net, masked_loss)
    dl = DataLoader(ds, batch_size=4, last_batch="pad")
    t0 = cached_step.trace_count()
    for xb, yb in dl:
        valid = dl.last_batch_valid
        mask = onp.zeros((xb.shape[0], 1), onp.float32)
        mask[:valid] = 1.0
        step(xb, yb, mx.nd.array(mask), batch_size=valid)
        assert step.last_step_compiled, step.last_fallback_reason
    assert cached_step.trace_count() - t0 == 1      # no tail retrace


# ---------------------------------------------------------------------------
# TrainStep bucketing (compile_step(bucket=True))
# ---------------------------------------------------------------------------
def _masked_loss(n_, x, y, m):
    return (((n_(x) - y) ** 2) * m).sum()


def test_train_step_bucket_parity_and_bounded_traces():
    """Variable-length batches with a pad-safe (masked) loss: params
    stay bit-exact vs unpadded eager training while the program cache
    holds one program per bucket instead of one per length.  The
    one-time verify compares the padded LOSS to the unpadded one up to
    summation order (``TrainStep._verify_pad``): the sum over (3, 4)
    and over (4, 4) with a zero row differ by 1 ulp here, which the
    verify used to take for a loss that is not pad-safe (PR 28)."""
    def build():
        net = _mlp(13, hybridize=True)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05})
        return net, tr

    netb, trb = build()
    step = trb.compile_step(netb, _masked_loss, bucket=True)
    nete, tre = build()
    rng = onp.random.RandomState(14)
    t0 = cached_step.trace_count()
    for n in (5, 6, 7, 8, 3):
        x = onp.asarray(rng.randn(n, 8), onp.float32)
        y = onp.asarray(rng.randn(n, 4), onp.float32)
        m = onp.ones((n, 1), onp.float32)
        step(mx.nd.array(x), mx.nd.array(y), mx.nd.array(m), batch_size=n)
        assert step.last_step_compiled, step.last_fallback_reason
        with mx.autograd.record():
            loss = _masked_loss(nete, mx.nd.array(x), mx.nd.array(y),
                                mx.nd.array(m))
        loss.backward()
        tre.step(n)
    assert step.bucket_refused is None
    assert step.padded_steps == 4                    # 8 was an exact fit
    assert cached_step.trace_count() - t0 == 2       # buckets {4, 8}
    for k, p in netb.collect_params().items():
        assert onp.array_equal(
            p.data().asnumpy(),
            nete.collect_params()[k].data().asnumpy()), k


def test_train_step_bucket_refuses_unmasked_mean_loss():
    """A mean loss is not pad-safe: the one-time loss-value verify
    catches it BEFORE any padded gradient is applied and training
    continues unpadded — numerics never silently change."""
    net = _mlp(15, hybridize=True)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    step = trainer.compile_step(
        net, lambda n_, x, y: ((n_(x) - y) ** 2).mean(), bucket=True)
    rng = onp.random.RandomState(16)
    x, y = mx.nd.array(rng.randn(5, 8)), mx.nd.array(rng.randn(5, 4))
    step(x, y, batch_size=5)
    assert step.last_step_compiled
    assert step.bucket_refused is not None
    assert "pad-safe" in step.bucket_refused
    assert step.padded_steps == 0


# ---------------------------------------------------------------------------
# CI gate
# ---------------------------------------------------------------------------
def test_dispatch_budget_serving_lane_smoke():
    """Tier-1 smoke for the gate's serving coverage: the INFER lane
    alone through the gate's own `_measure_infer`, held to
    INFER_BUDGET — 1 launch/batch, 0 retraces, programs <= buckets
    over the randomized variable-length stream.  The full lane matrix
    rides the slow lane (ISSUE-17 wall slice 2)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_dispatch_budget",
        os.path.join(REPO, "tools", "check_dispatch_budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    row = mod._measure_infer()
    assert row["bucket_refused"] is None
    for key, budget in mod.INFER_BUDGET.items():
        assert row[key] <= budget, (key, row[key], budget)


def test_dispatch_budget_gate_covers_serving():
    """tools/check_dispatch_budget.py (run like check_fault_sites): the
    serving path must hold 1 launch/batch, 0 retraces, and programs <=
    buckets over a randomized variable-length stream (the full lane
    matrix, 17 s; PR 28)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_dispatch_budget",
        os.path.join(REPO, "tools", "check_dispatch_budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "launches_per_batch" in mod.INFER_BUDGET
    assert mod.main() == 0
