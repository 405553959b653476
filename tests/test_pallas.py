"""Pallas flash-attention kernel tests (interpret mode on CPU — same code
path as TPU hardware)."""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.pallas_kernels import flash_attention


def _dense_attention(q, k, v, causal, sm_scale):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * sm_scale
    if causal:
        S = q.shape[1]
        mask = onp.tril(onp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,d", [(64, 16), (128, 32)])
def test_flash_forward_matches_dense(causal, seq, d):
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(3, seq, d), jnp.float32)
    k = jnp.asarray(rng.randn(3, seq, d), jnp.float32)
    v = jnp.asarray(rng.randn(3, seq, d), jnp.float32)
    sm_scale = 1.0 / d ** 0.5
    out = flash_attention(q, k, v, causal=causal)
    ref = _dense_attention(q, k, v, causal, sm_scale)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    rng = onp.random.RandomState(1)
    seq, d = 64, 16
    q = jnp.asarray(rng.randn(2, seq, d), jnp.float32)
    k = jnp.asarray(rng.randn(2, seq, d), jnp.float32)
    v = jnp.asarray(rng.randn(2, seq, d), jnp.float32)
    sm_scale = 1.0 / d ** 0.5
    tgt = jnp.asarray(rng.randn(2, seq, d), jnp.float32)

    def loss_flash(q, k, v):
        return ((flash_attention(q, k, v, causal=causal) - tgt) ** 2).mean()

    def loss_dense(q, k, v):
        return ((_dense_attention(q, k, v, causal, sm_scale) - tgt)
                ** 2).mean()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=5e-3, atol=1e-4,
                                    err_msg=f"d{name} mismatch")


def test_flash_4d_heads_and_jit():
    rng = onp.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 4, 32, 16), jnp.float32)  # B,H,S,D
    k = jnp.asarray(rng.randn(2, 4, 32, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 4, 32, 16), jnp.float32)
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))(
        q, k, v)
    assert out.shape == (2, 4, 32, 16)
    ref = _dense_attention(q.reshape(8, 32, 16), k.reshape(8, 32, 16),
                           v.reshape(8, 32, 16), True, 1 / 4.0)
    onp.testing.assert_allclose(onp.asarray(out).reshape(8, 32, 16),
                                onp.asarray(ref), rtol=2e-4, atol=2e-5)


def test_flash_bf16():
    rng = onp.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 64, 32), jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, 64, 32), jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, 64, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=False)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), False, 1 / 32 ** 0.5)
    onp.testing.assert_allclose(onp.asarray(out, onp.float32),
                                onp.asarray(ref), rtol=3e-2, atol=3e-2)


# -- dropout inside the kernels, operands in the caller's dtype -------------
def _dense_dropout_attention(q, k, v, p, key):
    """float32 reference with the SAME mask function as the kernels."""
    bh, s, d = q.shape
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    pr = jax.nn.softmax(jnp.einsum("bqd,bkd->bqk", q, k) / d ** 0.5, axis=-1)
    if p:
        pr = jnp.where(pk.dropout_keep_mask(key, bh, s, s, p), pr, 0.0) \
            / (1.0 - p)
    return jnp.einsum("bqk,bkd->bqd", pr, v)


_DROPOUT_PLANS = {
    "single_tile": ((2, 64, 16), ("rows", 1)),
    "several_heads_a_step": ((8, 32, 16), ("rows", 4)),
    "multi_block": ((2, 64, 16), ("blocks", 16, 32)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
@pytest.mark.parametrize("case", sorted(_DROPOUT_PLANS))
def test_flash_dropout_matches_dense_with_shared_mask(case, dropout_p, dtype):
    shape, plan = _DROPOUT_PLANS[case]
    key = jax.random.PRNGKey(5)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                  for kk in jax.random.split(jax.random.PRNGKey(1), 4))
    w32 = w.astype(jnp.float32)

    def flash(q, k, v):
        return pk._flash(q, k, v, pk._seed_words(key), False,
                         1 / shape[-1] ** 0.5, dropout_p, plan)

    out = flash(q, k, v)
    assert out.dtype == dtype
    got = (out,) + jax.grad(
        lambda *a: (flash(*a).astype(jnp.float32) * w32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    want = (_dense_dropout_attention(q, k, v, dropout_p, key),) + jax.grad(
        lambda *a: (_dense_dropout_attention(*a, dropout_p, key) * w32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2      # of each tensor's scale
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        b = onp.asarray(b, onp.float32)
        err = onp.abs(onp.asarray(a, onp.float32) - b).max()
        assert err <= tol * max(1.0, onp.abs(b).max()), (name, err)


def test_flash_dropout_mask_is_a_bernoulli_of_key_head_and_position():
    p, shape = 0.1, (6, 128, 128)
    mask = onp.asarray(pk.dropout_keep_mask(jax.random.PRNGKey(3), *shape, p))
    rate, n = mask.mean(), mask.size
    assert abs(rate - (1 - p)) <= 3 * (p * (1 - p) / n) ** 0.5, rate
    again = onp.asarray(pk.dropout_keep_mask(jax.random.PRNGKey(3), *shape, p))
    assert (mask == again).all()
    other = onp.asarray(pk.dropout_keep_mask(jax.random.PRNGKey(4), *shape, p))
    # two independent Bernoulli(0.9) masks differ at 2 p (1 - p) = 18%
    for a, b, what in ((mask, other, "key"), (mask[0], mask[1], "head"),
                       (mask[:, 0], mask[:, 1], "query position"),
                       (mask[:, :, 0], mask[:, :, 1], "key position")):
        assert 0.14 < (a != b).mean() < 0.22, what
    # a typed key and its raw words name the same mask
    typed = jax.random.key(3)
    assert (onp.asarray(pk.dropout_keep_mask(typed, *shape, p)) == onp.asarray(
        pk.dropout_keep_mask(jax.random.key_data(typed), *shape, p))).all()


def test_flash_dropout_does_not_depend_on_the_tiling():
    shape, key = (4, 64, 16), jax.random.PRNGKey(9)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(2), 3))

    def flash(plan):
        return onp.asarray(pk._flash(q, k, v, pk._seed_words(key), False,
                                     0.25, 0.1, plan))

    one_head, four_heads = flash(("rows", 1)), flash(("rows", 4))
    assert (one_head == four_heads).all()               # to the bit
    whole = one_head
    # the blocked kernels sum in another order: the same mask, to rounding
    onp.testing.assert_allclose(flash(("blocks", 16, 32)), whole,
                                rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(flash(("blocks", 32, 16)), whole,
                                rtol=1e-5, atol=1e-6)
    # two query-block sizes walk the keys in the same order: to the bit
    assert (flash(("blocks", 16, 32)) == flash(("blocks", 32, 32))).all()


def test_flash_public_entry_takes_the_key_and_checks_the_rate():
    q = jnp.ones((2, 16, 8), jnp.float32)
    key = jax.random.PRNGKey(0)
    dropped = flash_attention(q, q, q, causal=False, dropout_p=0.5,
                              dropout_key=key)
    assert not onp.allclose(onp.asarray(dropped), 1.0)  # v is all ones
    kept = flash_attention(q, q, q, causal=False, dropout_p=0.0)
    onp.testing.assert_allclose(onp.asarray(kept), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="dropout_key"):
        flash_attention(q, q, q, dropout_p=0.5)
    with pytest.raises(ValueError, match="dropout_p"):
        flash_attention(q, q, q, dropout_p=1.0, dropout_key=key)


@pytest.mark.parametrize("bh,seq,plan", [
    (384, 512, ("rows", 1)), (1536, 128, ("rows", 16)),
    (7, 96, ("rows", 7)), (8, 2048, ("blocks", 512, 512)),
    (3, 520, ("blocks", 104, 104)), (2, 1030, ("blocks", 206, 206))])
def test_flash_block_rule(bh, seq, plan):
    assert pk._plan(bh, seq) == plan


@pytest.mark.parametrize("seq,num_heads,head_dim,heads", [
    (512, 12, 64, 4), (128, 16, 64, 4), (16, 6, 64, 2), (16, 3, 64, None),
    (128, 8, 128, 4), (128, 8, 32, 4), (128, 16, 16, 8), (12, 8, 64, None),
    (1024, 8, 64, None), (128, 8, 20, None)])
def test_interleaved_heads_per_step_rule(seq, num_heads, head_dim, heads):
    assert pk.qkv_heads_per_step(seq, num_heads, head_dim) == heads


@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
def test_flash_qkv_is_flash_attention_over_the_interleaved_layout(dropout_p):
    seq, bsz, heads, d = 32, 2, 4, 32
    key = jax.random.PRNGKey(8)
    qkv, w = (jax.random.normal(k, shape, jnp.float32) for k, shape in zip(
        jax.random.split(jax.random.PRNGKey(6)),
        ((seq, bsz, heads * 3 * d), (seq, bsz, heads * d))))

    def split(qkv):
        x = qkv.reshape(seq, bsz, heads, 3, d)
        out = flash_attention(*(x[:, :, :, j].transpose(1, 2, 0, 3)
                                for j in range(3)), causal=False,
                              dropout_p=dropout_p, dropout_key=key)
        return out.transpose(2, 0, 1, 3).reshape(seq, bsz, heads * d)

    def in_place(qkv):
        return pk.flash_attention_qkv(qkv, heads, dropout_p=dropout_p,
                                      dropout_key=key)

    onp.testing.assert_allclose(in_place(qkv), split(qkv), rtol=1e-5,
                                atol=1e-6)
    onp.testing.assert_allclose(
        jax.grad(lambda x: (in_place(x) * w).sum())(qkv),
        jax.grad(lambda x: (split(x) * w).sum())(qkv), rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="cannot take"):
        pk.flash_attention_qkv(qkv[:12], heads)


def test_transformer_uses_flash_when_forced():
    from mxnet_tpu import models

    cfg = models.TransformerLMConfig(
        vocab_size=128, num_layers=1, num_heads=2, hidden=32, mlp_hidden=64,
        max_len=32, dtype=jnp.float32, use_flash_attention=True)
    cfg_ref = models.TransformerLMConfig(
        vocab_size=128, num_layers=1, num_heads=2, hidden=32, mlp_hidden=64,
        max_len=32, dtype=jnp.float32, use_flash_attention=False)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(onp.random.RandomState(0).randint(0, 128, (2, 16)),
                       jnp.int32)
    out_flash, _ = models.forward(params, toks, cfg)
    out_ref, _ = models.forward(params, toks, cfg_ref)
    onp.testing.assert_allclose(onp.asarray(out_flash),
                                onp.asarray(out_ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash-attention fallback counter (models/transformer_lm.py)
# ---------------------------------------------------------------------------


def test_flash_fallback_counted_and_logged_once(monkeypatch, caplog):
    """Misaligned (seq, head_dim) on the auto path: the einsum fallback
    is COUNTED (flash_fallback_count) and logged once — no more silent
    MFU cliff.  Aligned geometry never counts."""
    import logging

    from mxnet_tpu import models
    from mxnet_tpu.models import transformer_lm as tlm

    # the auto path only wants flash on a single-device TPU backend;
    # spoof the backend probe — the misaligned geometry means the Pallas
    # kernel itself is never invoked, only the fallback accounting runs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tlm, "_FLASH_FALLBACK_LOGGED", False)
    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=2, num_heads=4, hidden=36,  # head_dim 9
        mlp_hidden=32, max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    before = tlm.flash_fallback_count()
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.models"):
        models.forward(params, toks, cfg, None)
    assert tlm.flash_fallback_count() - before == cfg.num_layers
    msgs = [r.message for r in caplog.records
            if "flash_fallback_count" in r.message]
    assert len(msgs) == 1
    assert "head_dim=9" in msgs[0]
    # an explicitly-disabled flash never counts, even misaligned: the
    # counter tracks WANTED-but-blocked flash, not every einsum run
    cfg2 = models.TransformerLMConfig(
        vocab_size=64, num_layers=1, num_heads=4, hidden=36,
        mlp_hidden=32, max_len=16, dtype=jnp.float32,
        use_flash_attention=False)
    params2 = models.init_params(jax.random.PRNGKey(1), cfg2)
    c0 = tlm.flash_fallback_count()
    models.forward(params2, toks, cfg2, None)
    assert tlm.flash_fallback_count() == c0


def test_flash_fallback_not_counted_on_cpu_auto():
    """On the CPU backend the auto path never WANTS flash, so the
    counter must not fire (it tracks real fallbacks, not CPU runs)."""
    from mxnet_tpu import models
    from mxnet_tpu.models import transformer_lm as tlm

    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=1, num_heads=4, hidden=36,
        mlp_hidden=32, max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    c0 = tlm.flash_fallback_count()
    models.forward(params, jnp.zeros((2, 16), jnp.int32), cfg, None)
    assert tlm.flash_fallback_count() == c0


# ---------------------------------------------------------------------------
# no kernel without a caller
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _package_sources_outside_the_kernels():
    root = os.path.dirname(os.path.abspath(mx.__file__))
    own = os.path.abspath(pk.__file__)
    texts = {}
    for base, _dirs, files in os.walk(root):
        for f in files:
            path = os.path.join(base, f)
            if f.endswith(".py") and os.path.abspath(path) != own:
                with open(path) as fh:
                    texts[os.path.relpath(path, root)] = fh.read()
    return texts


@pytest.mark.parametrize("name", pk.__all__)
def test_every_public_kernel_has_a_caller(name):
    """A public name of ops/pallas_kernels.py is used by the package
    outside the kernels' own file: a kernel nothing calls is deleted,
    not kept for a bench (ROADMAP D3, PR 28)."""
    assert hasattr(pk, name)
    word = re.compile(r"\b%s\b" % re.escape(name))
    users = [path for path, text in
             _package_sources_outside_the_kernels().items()
             if word.search(text)]
    assert users, f"pallas_kernels.{name} has no caller in mxnet_tpu/"


@pytest.mark.parametrize("heads,d,kv_heads", [(20, 256, 20), (20, 256, 5),
                                              (15, 128, 15)],
                         ids=["group_of_one", "grouped", "fifteen_of_128"])
@pytest.mark.parametrize("seq", [48, 16, 80])
def test_grouped_causal_kernels_at_the_decoder_cells_head_shapes(
        seq, heads, d, kv_heads, monkeypatch):
    """``flash_attention_gqa`` as latent attention runs it: as many
    key-value heads as query heads (a group of one), heads of 256 (two
    128-lane columns a block), and the same width in groups of four (a
    group's heads share dk and dv); and as Olmo-Hybrid's full layer runs
    it on a tensor-parallel rank of two: 15 = 15 heads of 128, a width of
    1,920; one, three and five blocks a sequence,
    so the one backward kernel revisits a query block's dq after other
    blocks ran; values and the three gradients against the unfused
    expression, Pallas interpreter."""
    from mxnet_tpu.ops import contrib

    monkeypatch.setattr(pk, "_BLOCK", 16)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, ct = (jax.random.normal(key, (1, seq, heads * d)) * 0.3
             for key in ks[:2])
    k, v = (jax.random.normal(key, (1, seq, kv_heads * d)) * 0.3
            for key in ks[2:])
    got = pk.flash_attention_gqa(q, k, v, heads, kv_heads)
    want = contrib._unfused_causal_gqa(q, k, v, heads, kv_heads)
    onp.testing.assert_allclose(got, want, atol=2e-5)
    base = mx.telemetry.snapshot()
    grads = jax.grad(lambda *a: jnp.sum(pk.flash_attention_gqa(
        *a, heads, kv_heads) * ct), argnums=(0, 1, 2))(q, k, v)
    assert mx.telemetry.delta(base)["attention.gqa_backward_fused"] == 1
    wants = jax.grad(lambda *a: jnp.sum(contrib._unfused_causal_gqa(
        *a, heads, kv_heads) * ct), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, wants):
        onp.testing.assert_allclose(g, w, atol=5e-5)


def _scan_grid(x, B, chunk):
    """The grid of the forward scan kernel's ``pallas_call`` at these
    shapes."""
    from mxnet_tpu.ops import ssm

    b, l, h, _ = x.shape
    args = (x, jax.ShapeDtypeStruct((b, l, h), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32), B, B,
            jax.ShapeDtypeStruct((h,), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda *a: ssm._ssd_scan.__wrapped__(
        *a, chunk_size=chunk, fused=True))(*args).jaxpr

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    call, = calls(jaxpr)
    return tuple(call.params["grid_mapping"].grid)


def test_scan_head_blocks_by_shape():
    """A group's heads a grid step: Nemotron-H's 8 heads of 64 at chunks of
    128 stay ONE block on the grid (batch, group, chunk) it always had;
    Granite-4.0-H's one group of 32 heads of 64 at chunks of 256 takes two
    blocks of 16 on (batch, group, chunk, block); a block is a multiple of 8
    heads or the whole group."""
    assert pk.ssd_head_block(8, 64, 128) == 8
    assert pk.ssd_head_block(32, 64, 256) == 16
    assert pk.ssd_head_block(4, 64, 256) == 4
    assert pk.ssd_head_block(12, 128, 1024) == 12      # none fits: the group
    bf = jnp.bfloat16
    x = jax.ShapeDtypeStruct((1, 1024, 64, 64), bf)
    assert _scan_grid(x, jax.ShapeDtypeStruct((1, 1024, 8, 128), bf),
                      128) == (1, 8, 8)
    x = jax.ShapeDtypeStruct((1, 1024, 32, 64), bf)
    assert _scan_grid(x, jax.ShapeDtypeStruct((1, 1024, 1, 128), bf),
                      256) == (1, 1, 4, 2)


@pytest.mark.parametrize("h,g,chunk", [(32, 1, 256), (16, 2, 128)],
                         ids=["granite_one_group_of_32", "groups_of_8"])
def test_scan_kernels_in_head_blocks_equal_the_chunked_form(h, g, chunk):
    """The scan's kernel pair (Pallas interpreter) at Granite-4.0-H's one
    group of 32 heads of 64 in chunks of 256 (two blocks of 16: the group's
    dB and dC added over the blocks, each block's rows of dD) and at groups
    of 8 (one block), two chunks a sequence: y and all six gradients
    against XLA's chunked form."""
    from mxnet_tpu.ops import ssm

    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    seq, p, n = 2 * chunk, 64, 128
    args = (jax.random.normal(ks[0], (1, seq, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (1, seq, h)) - 3.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (h,))),
            0.3 * jax.random.normal(ks[3], (1, seq, g, n)),
            0.3 * jax.random.normal(ks[4], (1, seq, g, n)),
            jax.random.normal(ks[5], (h,)))
    ct = jax.random.normal(ks[6], (1, seq, h, p))

    def run(fused):
        def loss(*a):
            return jnp.sum(ssm._ssd_scan(*a, chunk_size=chunk, fused=fused)
                           * ct)
        with jax.default_matmul_precision("highest"):
            return (ssm._ssd_scan(*args, chunk_size=chunk, fused=fused),) \
                + jax.grad(loss, argnums=tuple(range(6)))(*args)

    for name, got, want in zip(("y", "x", "dt", "A", "B", "C", "D"),
                               run(True), run(False)):
        onp.testing.assert_allclose(got, want,
                                    atol=2e-5 * float(jnp.max(jnp.abs(want))),
                                    err_msg=name)


@pytest.mark.parametrize("scale", [None, 1 / 64])
def test_grouped_causal_kernels_at_heads_of_64(scale, monkeypatch):
    """``flash_attention_gqa`` as Granite-4.0-H's attention layer runs it on
    a tensor-parallel rank of two, at toy counts: query heads of 64 over
    key-value heads of 64 in groups of four, handed over head-major (no
    whole 128-lane column a head), at its own scale of the scores
    (``attention_multiplier``, a power of two) and at the default; three
    blocks a sequence; values and the three gradients against the unfused
    expression, Pallas interpreter."""
    from mxnet_tpu.ops import contrib

    monkeypatch.setattr(pk, "_BLOCK", 16)
    heads, kv_heads, d, seq = 8, 2, 64, 48
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, ct = (jax.random.normal(key, (2, seq, heads * d)) * 0.5
             for key in ks[:2])
    k, v = (jax.random.normal(key, (2, seq, kv_heads * d)) * 0.5
            for key in ks[2:])
    got = pk.flash_attention_gqa(q, k, v, heads, kv_heads, scale)
    want = contrib._unfused_causal_gqa(q, k, v, heads, kv_heads, scale)
    onp.testing.assert_allclose(got, want, atol=2e-5)
    if scale is not None:            # the scale is the one asked for
        assert onp.abs(onp.asarray(got) - onp.asarray(
            contrib._unfused_causal_gqa(q, k, v, heads, kv_heads))).max() \
            > 1e-2
    base = mx.telemetry.snapshot()
    grads = jax.grad(lambda *a: jnp.sum(pk.flash_attention_gqa(
        *a, heads, kv_heads, scale) * ct), argnums=(0, 1, 2))(q, k, v)
    assert mx.telemetry.delta(base)["attention.gqa_backward_fused"] == 1
    wants = jax.grad(lambda *a: jnp.sum(contrib._unfused_causal_gqa(
        *a, heads, kv_heads, scale) * ct), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, wants):
        onp.testing.assert_allclose(g, w, atol=5e-5)
