"""Large-tensor sanity (reference tests/nightly/test_large_array.py —
there the point is int64 indexing past 2^32 elements; XLA owns indexing
here, so these verify the FRAMEWORK layer at CI-feasible sizes: shape
arithmetic, gather/take row math, reductions, and serialization stay
exact at multi-million-element scale)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

N = 1 << 22            # 4M elements (~16 MB fp32) per array


def test_large_elementwise_and_reduction():
    # ones, not arange: 2N = 2^23 stays exactly representable in fp32
    x = nd.ones((N,))
    s = float((x * 2).sum().asnumpy())
    assert s == 2.0 * N


def test_large_take_rows():
    table = nd.reshape(nd.arange(N, dtype="float32"), shape=(1 << 16, 64))
    idx = nd.array(onp.array([0, 1, (1 << 16) - 1], onp.int32))
    rows = nd.take(table, idx)
    onp.testing.assert_allclose(rows.asnumpy()[2, -1], N - 1)


def test_large_argsort_tail():
    rng = onp.random.RandomState(0)
    x = nd.array(rng.rand(1 << 20).astype(onp.float32))
    top = nd.topk(x, k=3, ret_typ="value")
    v = onp.sort(x.asnumpy())[-3:][::-1]
    onp.testing.assert_allclose(top.asnumpy(), v, rtol=1e-6)


def test_shape_size_array_int64_no_truncation():
    """shape_array/size_array return true int64 (reference
    elemwise_unary_op.h) — no silent x32 truncation, and a logical size
    past 2**31 must not wrap (checked via jit tracing so no 8-GiB alloc)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import tensor as T

    x = jnp.ones((3, 4))
    assert T.shape_array(x).dtype == jnp.int64
    assert T.size_array(x).dtype == jnp.int64
    assert int(T.size_array(x)[0]) == 12
    big = jax.ShapeDtypeStruct((1 << 16, 1 << 16), jnp.bfloat16)
    out = jax.eval_shape(T.size_array, big)
    assert out.dtype == jnp.int64


def test_large_save_load_roundtrip(tmp_path):
    x = nd.arange(N, dtype="float32")
    path = str(tmp_path / "big.nd")
    nd.save(path, {"x": x})
    back = nd.load(path)["x"]
    assert back.shape == (N,)
    onp.testing.assert_allclose(back.asnumpy()[-5:], x.asnumpy()[-5:])


def test_large_embedding_gradient_rows():
    """Embedding over a big table: only touched rows get gradient mass."""
    from mxnet_tpu import autograd

    table = nd.zeros((1 << 15, 8))
    table.attach_grad()
    idx = nd.array(onp.array([7, 9, (1 << 15) - 1], onp.int32))
    with autograd.record():
        out = nd.Embedding(idx, table, input_dim=1 << 15, output_dim=8)
        loss = out.sum()
    loss.backward()
    g = table.grad.asnumpy()
    assert g[7].sum() == 8 and g[9].sum() == 8 and g[-1].sum() == 8
    assert onp.abs(g).sum() == 24


@pytest.mark.tpu
def test_past_int32_indexing_on_chip():
    """>2^31-element array in HBM: index write/read, take, slice and a
    full reduction past the int32 boundary (the reference nightly
    test_large_array.py int64 families, runnable here only where HBM
    allows — run it through the chip tool with MXNET_TEST_ALLOW_TPU=1).
    """
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("needs TPU HBM for a 4 GiB array")
    NBIG = (1 << 31) + 128                  # 4 GiB + eps in bf16
    x = nd.zeros((NBIG,), dtype="bfloat16")
    # Static write at a >int32 flat offset: XLA addresses large buffers
    # with s64 offsets internally, so constant indices past 2^31 are the
    # honest per-element path on TPU (runtime indices are int32 without
    # x64 — exercised below on a 2-D view where every dim fits int32,
    # which is also how the framework shapes real >2^31 workloads).
    x[NBIG - 3] = 7.0
    # full reduction over 2^31+ elements (fp32 accumulation, exact here)
    assert float(x.sum().asnumpy()) == 7.0
    # static slice starting past int32
    tail = x[NBIG - 8:].asnumpy().astype(onp.float32)
    assert tail.shape == (8,) and tail[5] == 7.0
    # runtime int64 index array past 2^31: the invoke-level x64 dispatch
    # rule must keep the indices s64 (without it, jax silently wraps them
    # to int32 and the gather lands at the wrong offset)
    got = nd.take(x, nd.array(onp.array([NBIG - 3, 2], onp.int64)))
    onp.testing.assert_allclose(got.asnumpy().astype(onp.float32), [7.0, 0.0])
    # getitem with a runtime int64 index array routes through the same
    # factorization (review finding: it used to silently wrap)
    got = x[nd.array(onp.array([NBIG - 3, 2], onp.int64))]
    onp.testing.assert_allclose(got.asnumpy().astype(onp.float32), [7.0, 0.0])
    # in-int32-range scalar writes (int and contiguous slice) go through
    # the masked elementwise path — a plain scatter's full-buffer copy
    # along the >2^31 dim is corrupt on this runtime (review finding:
    # these used to raise outright on TPU)
    x[0:4] = 1.0
    x[5] = 2.0
    assert float(x.sum().asnumpy()) == 13.0
    head = x[0:8].asnumpy().astype(onp.float32)
    onp.testing.assert_allclose(head, [1, 1, 1, 1, 0, 2, 0, 0])
    # 2-D view: runtime row gather where rows * cols exceeds int32 but
    # each index fits int32 (rows = 2^24 + 1)
    rows = NBIG // 128
    y = x.reshape((rows, 128))
    row = nd.take(y, nd.array(onp.array([rows - 1], onp.int32)))
    assert row.shape == (1, 128)
    got = row.asnumpy().astype(onp.float32)
    assert got[0, 125] == 7.0 and got.sum() == 7.0


def test_int64_values_past_int32_survive_creation():
    """Regression: NDArray creation from int64 data must keep values
    past 2^31 exact on every platform.  The device_put used to run
    OUTSIDE the enable_x64 scope, and the transfer then canonicalized
    through int32 — wrapping the VALUE while still reporting an int64
    dtype (caught live on a TPU: graph/edge-id scale data silently
    corrupted)."""
    big = (1 << 31) + 125
    a = nd.array(onp.array([big, 2, -big], onp.int64))
    assert str(a.dtype) in ("int64", "<class 'numpy.int64'>") or a.dtype == onp.int64
    onp.testing.assert_array_equal(a.asnumpy(), [big, 2, -big])
    # same contract for uint64 above 2^63 is out of scope (jax caps at
    # u64), but u64 past 2^32 must also survive
    b = nd.array(onp.array([1 << 40], onp.uint64))
    onp.testing.assert_array_equal(b.asnumpy(), [1 << 40])
