"""The attention core of the Gluon BERT path as one operator
(``interleaved_selfatt``): its two paths agree, ``BERTSelfAttention`` runs it
hybridized and through ``Trainer.compile_step`` with the Pallas kernel forced
(interpret mode on the CPU), the counters and the ``fallback`` event say
which path a trace took, AMP classifies it, and Mosaic compiles the kernels
at the benchmark's widths for a described v5e."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, nd, telemetry
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.ops import contrib
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import mesh as pmesh

HEADS, HEAD_DIM = 2, 64           # two heads fill a 128-lane row


@pytest.fixture
def as_tpu(monkeypatch):
    """The operator's platform test says TPU; the kernels still see the CPU
    and run under the Pallas interpreter.  The event buffer is the
    process's: a fallback another file's test provoked on purpose (the
    worker ran it first) is not this test's."""
    monkeypatch.setattr(contrib, "_attention_platform", lambda: "tpu")
    telemetry.clear_events()


def _counts():
    snap = telemetry.snapshot()
    return snap["attention.fused"], snap["attention.unfused"]


def _fallbacks():
    return telemetry.events("fallback", "attention.fused")


def _qkv(seq, bsz, dtype, seed=0):
    rng = onp.random.RandomState(seed)
    return jnp.asarray(rng.randn(seq, bsz, 3 * HEADS * HEAD_DIM), dtype)


def _core(qkv, key, training):
    return contrib.interleaved_selfatt(qkv, key, heads=HEADS, p=0.1,
                                       training=training)


# -- the two paths ----------------------------------------------------------
@pytest.mark.parametrize("training", [False, True], ids=["eval", "train"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_fused_and_unfused_paths_agree(monkeypatch, dtype, training):
    qkv = _qkv(16, 2, dtype)
    key = jnp.asarray([5, 9], jnp.uint32)
    w = jnp.asarray(onp.random.RandomState(1).randn(16, 2, HEADS * HEAD_DIM),
                    jnp.float32)

    def run(platform):
        monkeypatch.setattr(contrib, "_attention_platform", lambda: platform)
        out = _core(qkv, key, training)
        grad = jax.grad(lambda x: (_core(x, key, training)
                                   .astype(jnp.float32) * w).sum())(qkv)
        return out, grad

    fused, unfused = run("tpu"), run("cpu")
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b, name in zip(fused, unfused, ("out", "dqkv")):
        assert a.dtype == dtype and a.shape == b.shape
        b = onp.asarray(b, onp.float32)
        err = onp.abs(onp.asarray(a, onp.float32) - b).max()
        assert err <= tol * max(1.0, onp.abs(b).max()), (name, err)


def test_matches_the_two_interleaved_ops_and_drops_only_in_training():
    qkv = nd.array(onp.random.RandomState(2).randn(16, 2, 384)
                   .astype("float32"))
    scores = nd.contrib.interleaved_matmul_selfatt_qk(qkv, heads=HEADS)
    want = nd.contrib.interleaved_matmul_selfatt_valatt(
        qkv, nd.softmax(scores, axis=-1), heads=HEADS).asnumpy()
    # the frontend draws the key itself, as it does for Dropout
    got = nd.contrib.interleaved_selfatt(qkv, heads=HEADS, p=0.1).asnumpy()
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    a = nd.contrib.interleaved_selfatt(qkv, heads=HEADS, p=0.1,
                                       training=True).asnumpy()
    b = nd.contrib.interleaved_selfatt(qkv, heads=HEADS, p=0.1,
                                       training=True).asnumpy()
    assert not onp.allclose(a, want) and not onp.allclose(a, b)


def test_unfused_dropout_is_the_kernels_mask():
    qkv = _qkv(16, 2, jnp.float32, seed=3)
    key = jnp.asarray([1, 2], jnp.uint32)
    x = qkv.reshape(16, 2, HEADS, 3, HEAD_DIM)
    q, k, v = (x[:, :, :, j].transpose(1, 2, 0, 3)
               .reshape(2 * HEADS, 16, HEAD_DIM) for j in range(3))
    att = jax.nn.softmax(jnp.einsum("bqd,bkd->bqk", q, k) / HEAD_DIM ** 0.5)
    att = jnp.where(pk.dropout_keep_mask(key, 2 * HEADS, 16, 16, 0.1), att,
                    0) / 0.9
    want = jnp.einsum("bqk,bkd->bqd", att, v).reshape(2, HEADS, 16, HEAD_DIM)
    want = want.transpose(2, 0, 1, 3).reshape(16, 2, HEADS * HEAD_DIM)
    onp.testing.assert_allclose(onp.asarray(_core(qkv, key, True)),
                                onp.asarray(want), rtol=1e-5, atol=1e-6)


# -- the block, hybridized and compiled -------------------------------------
def _attention_block(dropout=0.1):
    net = bert.BERTSelfAttention(HEADS * HEAD_DIM, HEADS, dropout=dropout)
    net.initialize(mx.init.Xavier())
    return net


def test_block_hybridized_runs_the_kernel(as_tpu):
    net = _attention_block()
    x = nd.array(onp.random.RandomState(4).randn(2, 16, HEADS * HEAD_DIM)
                 .astype("float32"))
    eager = net(x).asnumpy()
    fused0, unfused0 = _counts()
    net.hybridize()
    hybrid = net(x).asnumpy()
    onp.testing.assert_allclose(hybrid, eager, rtol=1e-5, atol=1e-6)
    with autograd.record():
        first = net(x).asnumpy()
    with autograd.record():
        second = net(x).asnumpy()
    # training drops, with a fresh key a call; prediction does not
    assert not onp.allclose(first, eager) and not onp.allclose(first, second)
    onp.testing.assert_allclose(net(x).asnumpy(), eager, rtol=1e-5, atol=1e-6)
    fused, unfused = _counts()
    assert fused > fused0 and unfused == unfused0
    assert not _fallbacks()


def _train_tiny_bert(steps=8):
    net = bert.BERTModel(vocab_size=64, units=HEADS * HEAD_DIM, mlp_units=64,
                         num_layers=2, num_heads=HEADS, max_len=16,
                         dropout=0.1)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2}, kvstore="tpu")
    step = trainer.compile_step(net, lambda n, x, y: ((n(x) - y) ** 2).mean())
    rng = onp.random.RandomState(5)
    toks = nd.array(rng.randint(0, 64, (8, 16)), dtype="int32")
    y = nd.array(rng.randn(8, 16, HEADS * HEAD_DIM).astype("float32"))
    losses = [float(step(toks, y, batch_size=8).asscalar())
              for _ in range(steps)]
    assert step.last_step_compiled, step.last_fallback_reason
    assert all(map(onp.isfinite, losses)) and losses[-1] < losses[0], losses


def test_block_through_compile_step_with_the_kernel(as_tpu, monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")        # one chip, as a cell
    telemetry.clear_events()
    fused0, unfused0 = _counts()
    _train_tiny_bert()
    # one count a site a trace: two layers, and nothing unfused
    assert _counts() == (fused0 + 2, unfused0) and not _fallbacks()


def test_compile_step_under_a_mesh_keeps_the_unfused_expression(as_tpu):
    """kvstore='tpu' over the suite's 8 virtual devices is a dp=8 mesh:
    ``pallas_call`` cannot be partitioned, so every site says why."""
    telemetry.clear_events()
    fused0, unfused0 = _counts()
    _train_tiny_bert(steps=3)
    assert _counts() == (fused0, unfused0 + 2)
    assert [e["why"] for e in _fallbacks()] == ["mesh of 8 devices"] * 2


# -- which path, and what says so -------------------------------------------
def test_counters_and_fallback_event(as_tpu):
    key = jnp.zeros((2,), jnp.uint32)
    telemetry.clear_events()
    fused0, unfused0 = _counts()
    _core(_qkv(16, 2, jnp.float32), key, True)
    assert _counts() == (fused0 + 1, unfused0) and not _fallbacks()

    _core(_qkv(12, 2, jnp.float32), key, True)         # 12 % 8 != 0
    assert _counts() == (fused0 + 1, unfused0 + 1)
    (event,) = _fallbacks()
    # "seq" is the bus's own key: the field is stored as x_seq
    assert (event["x_seq"], event["head_dim"]) == (12, HEAD_DIM)
    assert "multiples of 8" in event["why"]

    telemetry.clear_events()
    with pmesh.mesh_scope(pmesh.make_mesh({"dp": 2})):
        _core(_qkv(16, 2, jnp.float32), key, True)
    assert _counts() == (fused0 + 1, unfused0 + 2)
    (event,) = _fallbacks()
    assert "mesh of 2 devices" in event["why"]
    # a mesh of one device is no mesh
    with pmesh.mesh_scope(pmesh.make_mesh({"dp": 1})):
        _core(_qkv(16, 2, jnp.float32), key, True)
    assert _counts() == (fused0 + 2, unfused0 + 2)


def test_cpu_takes_the_unfused_path_without_an_event():
    telemetry.clear_events()
    fused0, unfused0 = _counts()
    _core(_qkv(12, 2, jnp.float32), jnp.zeros((2,), jnp.uint32), True)
    assert _counts() == (fused0, unfused0 + 1) and not _fallbacks()


# -- AMP --------------------------------------------------------------------
def test_amp_casts_qkv_down_and_keeps_the_key():
    from op_smoke_specs import SPECS

    assert "interleaved_selfatt" in amp.lists.LOW_PRECISION_FUNCS
    assert "interleaved_selfatt" in SPECS
    assert get_op("interleaved_selfatt").rng_input
    qkv = nd.array(onp.random.RandomState(6).randn(16, 2, 384)
                   .astype("float32"))
    key = nd.array(onp.array([3, 4], "uint32"), dtype="uint32")
    plain = nd.contrib.interleaved_selfatt(qkv, key, heads=HEADS, p=0.1,
                                           training=True)
    amp.init("bfloat16")
    try:
        low = nd.contrib.interleaved_selfatt(qkv, key, heads=HEADS, p=0.1,
                                             training=True)
    finally:
        amp.uninit()
    assert plain.dtype == onp.float32 and str(low.dtype) == "bfloat16"
    # the same key, so the same mask: only bf16 rounding apart
    onp.testing.assert_allclose(low.astype("float32").asnumpy(),
                                plain.asnumpy(), rtol=5e-2, atol=5e-2)


# -- Mosaic, for a described v5e (no chip) ----------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("seq,bsz", [(512, 32), (128, 128)],
                         ids=["s512", "s128"])
def test_mosaic_compiles_the_cells_attention(one_chip, as_tpu, monkeypatch,
                                             seq, bsz):
    """``BERTSelfAttention`` at the benchmark's widths (bf16, 12 heads of 64,
    dropout 0.1), forward and backward, as a step stages it: the program
    holds the two Mosaic calls, and XLA neither transposes nor re-tiles an
    activation around them (the block's transposes sit next to the
    operator, whose kernels undo them: batch-major blocks are the layout
    the projections write)."""
    from mxnet_tpu.gluon import block as gblock

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    net = bert.BERTSelfAttention(768, 12, dropout=0.1)
    net.initialize()
    net.cast("bfloat16")
    params = net.collect_params()
    x = nd.zeros((1, 8, 768), dtype="bfloat16")
    raw_fn, _, _ = gblock._stage_fn(net, params, list(params),
                                    gblock._flatten_args((x,))[1], True,
                                    x.ctx)

    def loss(weights, x, key):
        (out,), _ = raw_fn(weights, [x], key)
        return out.astype(jnp.float32).sum()

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1)),
        [shape(p.shape) for p in params.values()], shape((bsz, seq, 768)),
        shape((2,), jnp.uint32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " transpose(%" not in text                  # the HLO opcode
    copied = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
              if math.prod(map(int, dims.split(","))) >= bsz * seq * 768]
    assert not copied, copied


VOCAB = 30528


@pytest.mark.parametrize("seq,bsz", [(512, 32), (128, 128)],
                         ids=["s512", "s128"])
def test_v5e_head_and_loss_write_no_float32_vocabulary_tensor(one_chip, seq,
                                                              bsz):
    """``BERTMaskedLMHead`` and ``SoftmaxCrossEntropyLoss`` under bf16 AMP
    at the cells' shapes, loss and gradients as a step takes them: no
    instruction of the optimized program writes a float32 array of
    (tokens x vocabulary) size, and the logits stay as the decoder wrote
    them.  The largest float32 arrays with a 30,528 axis that belong there
    are the decoder's weight and its gradient, (30,528 x 768).

    With the loss among the outputs the parent's
    ``-pick(log_softmax(x), y)`` fails this here as it did in the chip's
    step program (``f32[128,128,30528]``, ``fusion.332`` there; PERF.md
    section 6, PR 27); with the gradients alone the forward's gather is
    dead code and the parent passes, so the loss has to be an output."""
    from mxnet_tpu.gluon import block as gblock

    class HeadAndLoss(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.head = bert.BERTMaskedLMHead(VOCAB, units=768)
            self.ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def forward(self, hidden, labels):
            return self.ce(self.head(hidden), labels).mean()

    net = HeadAndLoss()
    net.initialize()
    params = net.collect_params()
    x = nd.zeros((1, 8, 768), dtype="bfloat16")
    y = nd.zeros((1, 8), dtype="int32")
    raw_fn, _, _ = gblock._stage_fn(net, params, list(params),
                                    gblock._flatten_args((x, y))[1], True,
                                    x.ctx)

    def loss(weights, x, y, key):
        (out,), _ = raw_fn(weights, [x, y], key)
        return out

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fused0 = telemetry.snapshot()["loss.sparse_ce.fused"]
    amp.init("bfloat16")
    try:
        text = _compiled_text(
            jax.value_and_grad(loss, argnums=(0, 1)),
            [shape(p.shape, jnp.float32) for p in params.values()],
            shape((bsz, seq, 768), jnp.bfloat16),
            shape((bsz, seq), jnp.int32), shape((2,), jnp.uint32))
    finally:
        amp.uninit()
    assert telemetry.snapshot()["loss.sparse_ce.fused"] == fused0 + 1
    # what an instruction of the entry computation writes: its type, up to
    # the opcode (a fused computation's own values never reach HBM)
    written = re.findall(r"^\s+(?:ROOT )?%\S+ = (.*?) [a-z][a-z0-9-]*\(",
                         text[text.index("\nENTRY "):], re.M)
    wide = [dims for out in written
            for dims in re.findall(r"f32\[([\d,]+)\]", out)
            if str(VOCAB) in dims.split(",")
            and math.prod(map(int, dims.split(","))) > VOCAB * 768]
    assert not wide, wide
    assert any(f"bf16[{bsz},{seq},{VOCAB}]" in out for out in written)


@pytest.mark.parametrize("bh,seq,calls", [(384, 128, 2), (8, 2048, 3)],
                         ids=["rows", "blocked"])
def test_mosaic_compiles_the_split_head_kernels(one_chip, monkeypatch, bh,
                                                seq, calls):
    """``flash_attention`` as ``transformer_lm`` and long sequences call
    it: whole-row kernels to 512 keys, the blocked three beyond."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((bh, seq, 64), jnp.bfloat16, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def loss(q, k, v, key):
        return pk.flash_attention(q, k, v, causal=False, dropout_p=0.1,
                                  dropout_key=key).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          x, x, x, key)
    assert text.count("tpu_custom_call") == calls


def test_v5e_resnet_stem_is_recomputed_and_no_activation_is_float32(one_chip):
    """Stem and first bottleneck of ``resnet50_v1(layout="NHWC")`` under bf16
    AMP at batch 32, forward and backward as a step stages them (here and
    not in a file of its own: a test worker loads the TPU's compiler once,
    so every compile for the described chip shares ``one_chip``'s file).
    ``BatchNorm`` hands on the convolution's
    bf16, so the entry computation writes no float32 array larger than the
    input batch; and the stem is one rematerialised segment
    (``HybridSequential.recompute``), so no array of the stem's
    (32, 112, 112, 64) that the forward pass writes is read by the backward
    pass: it writes its own.  At the parent the 16 float32 block outputs
    fail the first assert and the stem convolution's output the second."""
    from mxnet_tpu.gluon import block as gblock
    from mxnet_tpu.gluon.model_zoo import vision

    bsz, stem = 32, "bf16[32,112,112,64]"
    net = vision.ResNetV1(vision.BottleneckV1, [1], [64, 256], classes=10,
                          layout="NHWC", input_layout="NHWC")
    net.initialize()
    x = nd.zeros((1, 224, 224, 3))
    net(x)                                  # resolves the deferred shapes
    params = net.collect_params()
    raw_fn, _, _ = gblock._stage_fn(net, params, list(params),
                                    gblock._flatten_args((x,))[1], True,
                                    x.ctx)

    def loss(weights, x, key):
        (out,), _ = raw_fn(weights, [x], key)
        return out.astype(jnp.float32).sum()

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    low0 = telemetry.snapshot()["amp.batch_norm.low_precision"]
    amp.init("bfloat16")
    try:
        text = _compiled_text(
            jax.value_and_grad(loss),
            [shape(p.shape) for p in params.values()],
            shape((bsz, 224, 224, 3)), shape((2,), jnp.uint32))
    finally:
        amp.uninit()
    # stem, three in the body, one on the shortcut
    assert telemetry.snapshot()["amp.batch_norm.low_precision"] == low0 + 5
    entry = re.findall(
        r"^\s+(?:ROOT )?(%\S+) = (.*?) [a-z][a-z0-9-]*\((.*)$",
        text[text.index("\nENTRY "):], re.M)
    wide = [dims for _, out, _ in entry
            for dims in re.findall(r"f32\[([\d,]+)\]", out)
            if math.prod(map(int, dims.split(","))) > bsz * 224 * 224 * 3]
    assert not wide, wide
    # the entry computation is in schedule order: follow every stem-shaped
    # array a forward instruction writes, through XLA's own copies, slices
    # and concatenations (they carry no op_name), and see that no backward
    # instruction reads one
    forward_born, readers = set(), []
    for name, out, rest in entry:
        op_name = re.search(r'op_name="([^"]*)"', rest)
        operands = set(re.findall(r"%[\w.\-]+", rest.split(", metadata")[0]))
        if op_name and "transpose(" in op_name.group(1):
            readers += [(name, o) for o in operands & forward_born]
        elif stem in out if op_name else operands & forward_born:
            forward_born.add(name)
    assert forward_born                      # the forward pass did write it
    assert not readers, readers


def _grouped_causal_text(one_chip, seq, heads, kv_heads, d):
    """The compiled text of ``flash_attention_gqa``'s forward and backward
    at bf16 over ``seq`` keys."""
    q = jax.ShapeDtypeStruct((1, seq, heads * d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, kv_heads * d), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return pk.flash_attention_gqa(q, k, v, heads, kv_heads) \
            .astype(jnp.float32).sum()

    return _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


# a quarter of the cells' 8,192 keys, and all of them: the fused backward
# keeps a head's dq over the WHOLE sequence in VMEM, so only the full length
# shows that Mosaic takes it
@pytest.mark.parametrize("seq", [2048, 8192])
def test_mosaic_compiles_the_grouped_causal_kernels(one_chip, monkeypatch,
                                                    seq):
    """``flash_attention_gqa`` at the state-space cell's widths (32 query
    heads over 2 key-value heads of 128, bf16), forward and backward: two
    Mosaic calls, the forward and the ONE backward kernel (in blocks of
    1,024 at 8,192 keys, 12 MiB of float32 accumulators: dq of a head, dk
    and dv of a key-value head that 16 heads share), and the two key-value
    heads go in as they are."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert pk._gqa_bwd_block(8192, 512) == 1024
    assert pk._gqa_bwd_resident(8192, 128, 16, 1024) == 12 << 20
    text = _grouped_causal_text(one_chip, seq, 32, 2, 128)
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("heads,kv_heads,d", [(4, 4, 256), (32, 2, 128)],
                         ids=["group_of_one", "grouped"])
def test_mosaic_compiles_the_split_backward_past_the_budget(
        one_chip, monkeypatch, heads, kv_heads, d):
    """32,768 keys: the fused backward's accumulators (34 MiB at heads of
    256 in groups of one, 48 MiB at 128 in groups of 16) do not fit
    ``_GQA_BWD_VMEM``, so the backward is the dq kernel and the dk/dv
    kernel, three Mosaic calls with the forward, and says so."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    seq = 32768
    assert pk._gqa_bwd_resident(seq, d, heads // kv_heads, 1024) \
        > pk._GQA_BWD_VMEM
    base = telemetry.snapshot()
    text = _grouped_causal_text(one_chip, seq, heads, kv_heads, d)
    assert text.count("tpu_custom_call") == 3
    moved = telemetry.delta(base)
    assert (moved["attention.gqa_backward_fused"],
            moved["attention.gqa_backward_split"]) == (0, 1)


def _grouped_products_text(one_chip, tiles, k, n, fn=jax.value_and_grad):
    """The compiled text of ``grouped_matmul``'s forward and backward over a
    buffer of ``tiles`` tiles and 8 groups of (k, n) bf16 matrices."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w, group, used):
        return pk.grouped_matmul(x, w, group, used).astype(jnp.float32).sum()

    return _compiled_text(
        fn(loss, argnums=(0, 1)), spec((tiles * pk.GROUP_TILE, k)),
        spec((8, k, n)), spec((tiles,), jnp.int32), spec((1,), jnp.int32))


def _group_blocks(k, n):
    """(columns a forward step holds, columns the rows' gradient's step
    holds, rows of K the matrices' gradient accumulates) at bf16."""
    return (pk._group_cols(k, n, 2), pk._group_cols(n, k, 2),
            pk._group_rows(k, n, 2))


def test_mosaic_compiles_the_grouped_products(one_chip, monkeypatch):
    """``grouped_matmul`` at the held experts' widths of the state-space
    cell (8 experts, 2688 to the hidden width 1856 padded to 1920 lanes and
    back, the cell's buffer of 7,168 rows), forward and backward: the product with a
    group's whole 10.3 MB matrix resident (over Mosaic's default VMEM limit:
    the calls state theirs), the same kernel reading the matrix transposed
    for the rows (no transposed copy of the weights in the program) and the
    accumulating kernel for the matrices."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    for k, n in ((2688, 1920), (1920, 2688)):
        assert _group_blocks(k, n) == (n, k, k)        # everything resident
        text = _grouped_products_text(one_chip, 56, k, n, jax.grad)
        assert text.count("tpu_custom_call") == 2          # dx and dw
        both = _grouped_products_text(one_chip, 56, k, n)
        assert both.count("tpu_custom_call") == 3
        assert " transpose(" not in both


def test_mosaic_compiles_the_grouped_products_in_blocks(one_chip,
                                                        monkeypatch):
    """The same kernels where a group's matrix does NOT fit the budget (a
    sixth of it here, at the state-space cell's widths): column blocks on
    the outer grid dimension forward, row blocks of K for the matrices'
    gradient."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "_GROUP_VMEM", pk._GROUP_VMEM // 6)
    assert _group_blocks(2688, 1920) == (640, 896, 384)
    both = _grouped_products_text(one_chip, 28, 2688, 1920)
    assert both.count("tpu_custom_call") == 3


def test_mosaic_compiles_the_latent_cells_kernels(one_chip, monkeypatch):
    """The latent-attention cell's two kernels at its widths, bf16, forward
    and backward: ``flash_attention_gqa`` at 20 = 20 heads of 256 (blocks,
    scratch and the one backward kernel at twice the width it had run at,
    10 MiB of float32 accumulators) at a quarter of the 8,192 keys and at all
    of them, two Mosaic calls, and ``grouped_matmul`` at the gated experts'
    widths (8 experts, 2048 to gate and up side by side, 3072; 1536 back),
    every group's whole matrix resident."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pk._gqa_bwd_resident(8192, 256, 1, 1024) == 10 << 20
    base = telemetry.snapshot()
    for seq in (2048, 8192):
        text = _grouped_causal_text(one_chip, seq, 20, 20, 256)
        assert text.count("tpu_custom_call") == 2
    moved = telemetry.delta(base)
    assert (moved["attention.gqa_backward_fused"],
            moved["attention.gqa_backward_split"]) == (2, 0)
    # the gated experts' buffer of 17,408 rows; the matrices' gradient of the
    # 2048 x 3072 product is exactly what the budget holds
    for k, n in ((2048, 3072), (1536, 2048)):
        assert _group_blocks(k, n) == (n, k, k)
        both = _grouped_products_text(one_chip, 136, k, n)
        assert both.count("tpu_custom_call") == 3
        assert " transpose(" not in both


def test_mosaic_compiles_the_delta_rule_cells_core(one_chip, monkeypatch):
    """``flash_attention_gqa`` as the delta-rule cell's full-attention layer
    runs it on a tensor-parallel rank of two: 15 = 15 heads of 128 (a width
    of 1,920), bf16, 8,192 keys, forward and the ONE backward kernel (5 MiB
    of float32 accumulators: dq of a head over the sequence, dk and dv of
    one key block of 1,024)."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert pk.gqa_block(8192) == 512 and pk._gqa_bwd_block(8192, 512) == 1024
    assert pk._gqa_bwd_resident(8192, 128, 1, 1024) == 5 << 20
    base = telemetry.snapshot()
    text = _grouped_causal_text(one_chip, 8192, 15, 15, 128)
    assert text.count("tpu_custom_call") == 2
    moved = telemetry.delta(base)
    assert (moved["attention.gqa_backward_fused"],
            moved["attention.gqa_backward_split"]) == (1, 0)


def test_mosaic_compiles_the_scan_kernels(one_chip, monkeypatch):
    """``ssd_scan``'s kernel path at the state-space cell's widths (64 heads
    of 64 in 8 groups, state 128, chunk 128, bf16) at a quarter of its 8,192
    positions, forward and backward.  Forward: one Mosaic call.
    Differentiated: the forward that also writes the states, and the
    backward; and every instruction that carries a name stands under
    ``SsdScan``, the scope the benchmark's scan metrics read."""
    from mxnet_tpu.ops import ssm

    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((1, 2048, 64, 64)), spec((1, 2048, 64), jnp.float32),
            spec((64,), jnp.float32), spec((1, 2048, 8, 128)),
            spec((1, 2048, 8, 128)), spec((64,), jnp.float32))

    def scan(*a):
        return ssm._ssd_scan(*a, chunk_size=128, fused=True)

    def loss(*a):
        return scan(*a).astype(jnp.float32).sum()

    assert _compiled_text(scan, *args).count("tpu_custom_call") == 1
    text = _compiled_text(jax.grad(loss, argnums=tuple(range(6))), *args)
    assert text.count("tpu_custom_call") == 2
    names = re.findall(r'op_name="([^"]*)"', text[text.index("ENTRY"):])
    outside = [n for n in names if ssm.SCAN_SCOPE not in n
               and not re.fullmatch(r"a\[\d\]|jit\(\w+\)/"
                                    r"(transpose\(jvp\(\)\)/)?"
                                    r"(convert_element_type|reduce_sum|mul|"
                                    r"broadcast_in_dim)", n)]
    assert not outside, outside


def test_mosaic_compiles_the_delta_rule_kernels(one_chip, monkeypatch):
    """``gated_delta_rule``'s kernel pair at the delta-rule cell's shapes
    (8,192 positions in chunks of 64, 15 heads, keys of 96 and values of
    192, bf16 operands, float32 gates).  Forward: one Mosaic call.
    Differentiated inside a layer's recomputation (``jax.checkpoint``, as
    ``remat_call`` stages every layer of the cell): the forward, its
    recomputation and the backward, three calls; no ``while`` loop is left
    (XLA's form scans the 128 chunks), and every call stands under
    ``DeltaRule``, the scope the benchmark's rule metrics read."""
    from mxnet_tpu.ops import delta_rule as dr

    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((1, 8192, 15, 96)), spec((1, 8192, 15, 96)),
            spec((1, 8192, 15, 192)), spec((1, 8192, 15), jnp.float32),
            spec((1, 8192, 15), jnp.float32))

    def rule(*a):
        return dr._gated_delta_rule(*a, chunk_size=64, fused=True)

    def loss(*a):
        return jnp.square(jax.checkpoint(rule)(*a).astype(jnp.float32)).sum()

    assert _compiled_text(rule, *args).count("tpu_custom_call") == 1
    text = _compiled_text(jax.grad(loss, argnums=tuple(range(5))), *args)
    calls = re.findall(r'custom_call_target="tpu_custom_call".*'
                       r'op_name="([^"]*)"', text)
    assert len(calls) == 3
    assert all(dr.RULE_SCOPE in name for name in calls), calls
    assert " while(" not in text


def test_mosaic_compiles_the_granite_cells_kernels(one_chip, monkeypatch):
    """The Granite-4.0-H cell's two kernel paths at its widths, bf16, 8,192
    positions, forward and backward: the scan over ONE group of 32 heads of
    64 in chunks of 256 (the whole group's backward does not fit Mosaic's
    scoped VMEM: blocks of 16 heads a grid step), two Mosaic calls; and
    ``flash_attention_gqa`` at 16 query heads of 64 over 4, head-major, two
    Mosaic calls, the backward the one fused kernel."""
    from mxnet_tpu.ops import ssm

    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pk.ssd_head_block(32, 64, 256) == 16
    args = (spec((1, 8192, 32, 64)), spec((1, 8192, 32), jnp.float32),
            spec((32,), jnp.float32), spec((1, 8192, 1, 128)),
            spec((1, 8192, 1, 128)), spec((32,), jnp.float32))

    def loss(*a):
        return ssm._ssd_scan(*a, chunk_size=256, fused=True) \
            .astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=tuple(range(6))), *args)
    assert text.count("tpu_custom_call") == 2
    base = telemetry.snapshot()
    q = jax.ShapeDtypeStruct((1, 8192, 16 * 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4 * 64), jnp.bfloat16,
                              sharding=one_chip)

    def core(q, k, v):
        return pk.flash_attention_gqa(q, k, v, 16, 4, 1 / 64) \
            .astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(core, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") == 2
    moved = telemetry.delta(base)
    assert (moved["attention.gqa_backward_fused"],
            moved["attention.gqa_backward_split"]) == (1, 0)


# the three hybrid cells' convolutions: (batch, projection width, convolved
# channels from, cuts of the result, bias)
CONV_CELLS = {
    "nemotron_xbc": (1, 10304, 4096, (4096, 1024, 1024), True),
    "granite_xbc": (2, 4384, 2048, (2048, 128, 128), True),
    "olmo_keys": (1, 1440, 0, (1440,), False),
    "olmo_values": (1, 2880, 0, (2880,), False)}


@pytest.mark.parametrize("cell", list(CONV_CELLS))
def test_mosaic_compiles_the_conv_kernels(one_chip, monkeypatch, cell):
    """``causal_conv1d``'s kernel pair at the hybrid cells' widths, bf16,
    8,192 positions, forward and backward: the Mamba-2 mixers' convolution
    read out of the whole ``in_proj`` output and cut into x, B and C, the
    delta-rule mixer's three (1,440 and 2,880 channels, no bias).  Forward:
    one Mosaic call; differentiated with the output kept: the forward and
    the ONE backward call, both under ``CausalConv1d``, the scope the
    benchmark's mixer metrics read."""
    from mxnet_tpu.ops import ssm

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    bsz, width, begin, splits, bias = CONV_CELLS[cell]
    channels = sum(splits)
    assert pk.conv_channel_block((channels, begin) + splits, 8192, 2) \
        == (48 if channels == 1440 else 64)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((bsz, 8192, width)), spec((channels, 4), jnp.float32),
            spec((channels,), jnp.float32) if bias else None)

    def conv(x, w, b):
        return ssm._conv_fused(x, w, b, "silu", begin, splits)

    def loss(x, w, b):
        return sum(jnp.sum(o.astype(jnp.float32)) for o in conv(x, w, b))

    assert _compiled_text(conv, *args).count("tpu_custom_call") == 1
    text = _compiled_text(jax.value_and_grad(
        loss, argnums=(0, 1, 2) if bias else (0, 1)), *args)
    calls = re.findall(r'custom_call_target="tpu_custom_call".*'
                       r'op_name="([^"]*)"', text)
    assert len(calls) == 2
    assert all(ssm.CONV_SCOPE in name for name in calls), calls


@pytest.mark.parametrize("mixer,calls,copies", [
    ("nemotron", 4, 0), ("granite", 4, 0), ("olmo", 8, 8)])
def test_mosaic_mixer_layers_copy_no_more_for_the_conv_kernels(
        one_chip, monkeypatch, mixer, calls, copies):
    """One mixer layer at its cell's widths under ``amp.init``, recomputed
    as ``remat_call`` stages every layer, differentiated, every kernel
    forced: the Mamba-2 mixers hand their whole projection to the
    convolution and its x, B and C to the scan with no activation-sized
    copy in between (none either way, as under XLA's convolution); the
    delta-rule mixer makes the 8 copies of its L2 norms and rule it made
    under XLA's convolution, none larger."""
    from mxnet_tpu.gluon import block as gblock
    from mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHMamba2Mixer
    from mxnet_tpu.ops import delta_rule, ssm

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pmesh, "current_mesh", lambda: None)
    for module, name in ((ssm, "_conv_platform"), (ssm, "_scan_platform"),
                         (delta_rule, "_rule_platform")):
        monkeypatch.setattr(module, name, lambda: "tpu")
    if mixer == "nemotron":
        layer, bsz, hidden = NemotronHMamba2Mixer(2688, 64, 64, 8, 128), 1, \
            2688
    elif mixer == "granite":
        layer, bsz, hidden = NemotronHMamba2Mixer(
            2048, 32, 64, 1, 128, chunk_size=256), 2, 2048
    else:
        layer, bsz, hidden = gluon.nn.GatedDeltaNet(
            3840, 15, 96, 192, allow_neg_eigval=True), 1, 3840

    class Recomputed(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.layer = layer

        def forward(self, x):
            return gblock.remat_call([self.layer], x)

    net = Recomputed()
    net.initialize()
    params = net.collect_params()
    x = nd.zeros((1, 8, hidden), dtype="bfloat16")
    raw_fn, _, _ = gblock._stage_fn(net, params, list(params),
                                    gblock._flatten_args((x,))[1], True,
                                    x.ctx)

    def loss(weights, x, key):
        (out,), _ = raw_fn(weights, [x], key)
        return out.astype(jnp.float32).sum()

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    base = telemetry.snapshot()
    amp.init("bfloat16")
    try:
        text = _compiled_text(
            jax.grad(loss, argnums=(0, 1)),
            [shape(p.shape) for p in params.values()],
            shape((bsz, 8192, hidden), jnp.bfloat16),
            shape((2,), jnp.uint32))
    finally:
        amp.uninit()
    assert telemetry.delta(base)["ssm.conv_fused"] == \
        (3 if mixer == "olmo" else 1)
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    entry = text[text.index("\nENTRY "):]
    copied = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(",
                                          entry)
              if math.prod(map(int, dims.split(","))) >= bsz * 8192 * 1024]
    assert len(copied) == copies, copied
    assert all(math.prod(map(int, d.split(","))) <= bsz * 8192 * 2880
               for d in copied), copied
