#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process, plain ``python chip_smoke.py`` on a machine that holds a TPU.
It drives the two hot paths once through the entry points a user calls
(``import mxnet_tpu as mx``), at the full width of the models the repo
trains, checks what comes out by the repo's own means, and prints as the
LAST line of stdout::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (a failed check is a nonzero exit; nothing is retried or skipped):

- ``train/resnet50``   model-zoo ResNet-50 v1 (NHWC, 224, 1000 classes), bf16
                       AMP, ``Trainer(kvstore='tpu').compile_step``, batch
                       128, 1 compile + 5 steps
- ``train/bert_base``  model-zoo BERT-base + MLM head, dropout on, 32 x 128
- ``kernel/flash``     ``__graft_entry__.entry()`` as the driver jits it (the
                       Mosaic custom call must be in the compiled text),
                       then flash forward/backward against the einsum
                       reference at two shapes, and at the two shapes the
                       benchmark's BERT cells run with the dropout inside
                       (same-mask reference; ms a call beside the unfused
                       expression's); ``ops.random.keep_mask`` on the chip
                       against the CPU's, bit for bit
- ``serve/decode``     ``serving_decode.GenerativeEngine`` at the default
                       precision against the float32 eager oracle (token-exact
                       up to stated bf16 near ties), pool buffers donated
- ``mesh``             with >= 4 devices: resnet50 under ``dp=4`` and
                       bert_base under ``dp=2,fsdp=2``

Flags: ``--chips N`` makes the mesh phase mandatory (fails with fewer
devices); ``--kernels`` compiles every public Pallas kernel once against its
jnp reference instead of the phases above; ``--rehearse`` runs the same code
at toy sizes, permits the CPU (interpret-mode kernels), prefixes every line
with REHEARSAL and never prints the pass line.

The smoke reports counts, device identities and seconds of compile as
set-up.  It prints no rate, no utilization and no peak: those belong to the
benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PHASES = ("train/resnet50", "train/bert_base", "kernel/flash",
          "serve/decode", "mesh")

REAL = dict(rn_batch=128, rn_img=224, bert_batch=32, bert_seq=128,
            bert_layers=12, entry_layers=12,
            flash_shapes=((32 * 12, 128, 64), (8, 2048, 64)),
            # (seq, batch) of the benchmark's BERT cells: tok_s512, tok_s128
            cell_shapes=((512, 32), (128, 128)),
            kernel_batch=32)
TOY = dict(rn_batch=8, rn_img=32, bert_batch=4, bert_seq=16,      # --rehearse
           bert_layers=1, entry_layers=1,
           flash_shapes=((4, 128, 64), (2, 256, 64)),
           cell_shapes=((32, 2), (16, 4)), kernel_batch=2)

_PREFIX = ""


def say(msg: str = "") -> None:
    for line in str(msg).splitlines() or [""]:
        print(_PREFIX + line, flush=True)


def check(ok, what: str) -> None:
    say(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _platforms(arr) -> set:
    return {d.platform for d in arr.devices()}


# ---------------------------------------------------------------------------
# train phases (one-chip and mesh share this body)
# ---------------------------------------------------------------------------
def _resnet50(sz):
    import numpy as onp

    import mxnet_tpu as mx

    net = mx.gluon.model_zoo.vision.resnet50_v1(
        classes=1000, layout="NHWC", input_layout="NHWC")
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, sz["rn_img"], sz["rn_img"], 3)))  # deferred shapes
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    rng = onp.random.RandomState(0)
    b, img = sz["rn_batch"], sz["rn_img"]
    x = rng.rand(b, img, img, 3).astype(onp.float32)
    y = rng.randint(0, 1000, (b,)).astype(onp.int32)
    return (net, lambda n, d, l: ce(n(d), l).mean(), (x, y), "sgd",
            {"learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4})


def _bert_base(sz):
    import numpy as onp

    import mxnet_tpu as mx

    bert = mx.gluon.model_zoo.bert

    class MLM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            # bert_base() is 12 layers of this width; the rehearsal cuts
            # depth only
            self.encoder = (bert.bert_base() if sz["bert_layers"] == 12
                            else bert.BERTModel(
                                units=768, mlp_units=3072, num_heads=12,
                                num_layers=sz["bert_layers"]))
            self.head = bert.BERTMaskedLMHead(30528)

        def forward(self, tokens):
            return self.head(self.encoder(tokens))

    net = MLM()                       # dropout 0.1 stays on: a PRNG key
    net.initialize(mx.init.Xavier())  # enters the compiled program
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    rng = onp.random.RandomState(1)
    toks = rng.randint(0, 30528, (sz["bert_batch"], sz["bert_seq"])
                       ).astype(onp.int32)
    return (net, lambda n, t, l: ce(n(t), l).mean(), (toks, toks), "adam",
            {"learning_rate": 1e-4})


def train_phase(name, build, sz, mesh_spec=None, n_dev=1, steps=5):
    """1 compile + ``steps`` steps through Trainer.compile_step; returns
    the losses and first-call seconds."""
    import jax
    import numpy as onp

    import mxnet_tpu as mx

    gc.collect()    # the earlier phases' nets and buffers: their frames are gone
    cs, tel, spmd = mx.cached_step, mx.telemetry, mx.parallel.spmd
    on_tpu = jax.devices()[0].platform == "tpu"
    if mesh_spec is not None:
        os.environ["MXNET_SPMD_MESH"] = mesh_spec
    elif len(jax.devices()) > 1:
        # a one-chip phase on a several-chip host: 'auto' would shard it
        os.environ["MXNET_SPMD_MESH"] = "off"
    say(f"== {name}  (MXNET_SPMD_MESH="
        f"{os.environ.get('MXNET_SPMD_MESH', 'auto (unset)')})")
    mx.random.seed(0)
    snap0 = tel.snapshot()
    attn0 = {k: snap0[f"attention.{k}"] for k in ("fused", "unfused")}
    ce0 = snap0["loss.sparse_ce.fused"]
    net, loss_fn, (x_np, y_np), opt, opt_params = build(sz)
    trainer = mx.gluon.Trainer(net.collect_params(), opt, opt_params,
                               kvstore="tpu")
    step = trainer.compile_step(net, loss_fn)
    batch = step.batch_sharding
    if batch is None:
        # the way a user builds a batch: from numpy, default context
        x, y = mx.nd.array(x_np), mx.nd.array(y_np, dtype="int32")
    else:
        x, y = next(iter(mx.engine.prefetch([(x_np, y_np)], depth=0,
                                            sharding=batch)))
    bsz = int(x.shape[0])
    tel.clear_events()

    d0, t0 = cs.dispatch_count(), cs.trace_count()
    disk0 = dict(mx.program_store.disk_stats())
    t_c = time.perf_counter()
    last = step(x, y, batch_size=bsz)
    losses = [float(last.asnumpy())]            # host read = the fence
    first_s = time.perf_counter() - t_c
    disk1 = mx.program_store.disk_stats()
    say(f"  first call (trace + compile + step 0): {first_s:.1f} s; "
        f"persistent cache +{disk1['hits'] - disk0['hits']} hits "
        f"+{disk1['misses'] - disk0['misses']} misses")
    check(step.last_step_compiled and step.last_fallback_reason is None,
          "step 0 ran compiled (no fallback reason)")
    check(cs.dispatch_count() - d0 == 1, "step 0: 1 dispatch")
    say(f"  traces for the first program: {cs.trace_count() - t0}")
    r_warm = spmd.reshard_count()
    trainable = [p for p in net.collect_params().values()
                 if p.grad_req != "null"]
    for i in range(1, steps + 1):
        old = [p.data()._data for p in trainable]
        d0, t0 = cs.dispatch_count(), cs.trace_count()
        last = step(x, y, batch_size=bsz)
        losses.append(float(last.asnumpy()))
        check(step.last_step_compiled and step.last_fallback_reason is None
              and cs.dispatch_count() - d0 == 1
              and cs.trace_count() - t0 == 0,
              f"step {i}: compiled, 1.0 dispatch, 0 retraces")
        if on_tpu:
            check(all(o.is_deleted() for o in old),
                  f"step {i}: all {len(old)} previous weight buffers "
                  "donated (is_deleted)")
    if not on_tpu:
        say("  donation is off on the cpu backend: is_deleted not checked")
    say("  losses: " + " ".join(f"{l:.4f}" for l in losses))
    fallbacks = tel.events("fallback")
    if mesh_spec is not None:
        # pallas_call has no partitioning rule: under a mesh on a TPU every
        # BERT attention site keeps the unfused expression and says so
        meshed = [e for e in fallbacks if e["name"] == "attention.fused"
                  and e["why"].startswith("mesh of")]
        say(f"  attention sites that kept the unfused expression under the "
            f"mesh: {len(meshed)}")
        fallbacks = [e for e in fallbacks if e not in meshed]
    check(not fallbacks, "no 'fallback' event in telemetry")
    snap = tel.snapshot()
    fused, unfused = (snap[f"attention.{k}"] - attn0[k]
                      for k in ("fused", "unfused"))
    say(f"  attention sites traced: {fused} onto the Pallas kernel, "
        f"{unfused} as the unfused expression")
    if on_tpu and mesh_spec is None:
        check(unfused == 0, "one chip: no attention site left the kernel")
    check(snap["loss.sparse_ce.fused"] > ce0,
          "the loss traced as sparse_softmax_cross_entropy "
          f"({snap['loss.sparse_ce.fused'] - ce0} site-traces)")
    check(all(onp.isfinite(l) for l in losses), "every loss finite")
    check(losses[-1] < losses[0],
          f"loss fell: step {steps} {losses[-1]:.4f} < step 0 "
          f"{losses[0]:.4f}")
    check(spmd.reshard_count() == r_warm,
          "0 steady-state reshards (spmd.reshard_count flat after step 0)")

    params = [p.data()._data for p in net.collect_params().values()]
    states = [l for s in trainer._updaters[0].states.values()
              for l in jax.tree_util.tree_leaves(
                  mx.optimizer.fused._unwrap(s))]
    leaves = params + states + [x._data, y._data, last._data]
    want = {"tpu"} if on_tpu else {"cpu"}
    plats = set().union(*[_platforms(a) for a in leaves])
    check(plats == want,
          f"{len(params)} params, {len(states)} optimizer-state leaves, "
          f"the batch and the loss live on {sorted(plats)} only")
    if mesh_spec is not None:
        _mesh_checks(step, params, x, n_dev)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "smoke.params")
        net.save_parameters(path)       # reads every live buffer after
        net.load_parameters(path)       # the donating steps
        check(os.path.getsize(path) > 0,
              "save_parameters/load_parameters round trip after donation")
    return losses, first_s


def _mesh_checks(step, params, x, n_dev):
    import jax

    big = max(params, key=lambda a: a.size)
    check(len(big.sharding.device_set) == n_dev,
          f"a parameter's sharding spans {n_dev} devices")
    shard_devs = {s.device for s in x._data.addressable_shards}
    check(len(shard_devs) == n_dev,
          f"the batch's shards sit on {n_dev} distinct devices")
    devs = jax.devices()[:n_dev]
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    what = "bytes_in_use"
    if any(u is None for u in used):
        # the CPU backend reports no memory_stats: count live arrays
        what = "live-array bytes (this backend reports no memory_stats)"
        used = dict.fromkeys(devs, 0)
        for a in jax.live_arrays():
            for sh in a.addressable_shards:
                if sh.device in used:
                    used[sh.device] += sh.data.nbytes
        used = list(used.values())
    check(min(used) > 0 and max(used) <= 2 * min(used),
          f"{what} nonzero on every device and no device holds more than "
          f"twice another: {used}")
    text = "\n".join(rec.executable.as_text()
                     for rec in step._programs.values()
                     if rec.executable is not None)
    check("all-reduce" in text, "the compiled program contains an all-reduce")


def mesh_phase(sz, rehearse, rn_losses, first_calls):
    from mxnet_tpu.models import transformer_lm as tlm

    losses, first_calls["mesh/resnet50 dp=4"] = train_phase(
        "mesh/resnet50", _resnet50, sz, mesh_spec="dp=4", n_dev=4)
    # the loss is a bf16 value under AMP (2^-8 relative), so the
    # forward-only step 0 gets 1e-2 and the two trained steps 5e-2
    diffs = [abs(a - b) / max(abs(b), 1e-6)
             for a, b in zip(losses[:3], rn_losses[:3])]
    got = " ".join(f"{d:.1e}" for d in diffs)
    check(diffs[0] <= 1e-2,
          "dp=4 step-0 loss matches the one-chip phase at the same "
          f"global batch within 1e-2 relative (got {got})")
    if rehearse:
        say("  steps 1-2 not compared at toy sizes: an 8-image 32-pixel "
            "batch trains chaotically")
    else:
        check(max(diffs[1:]) <= 5e-2,
              "dp=4 losses of steps 1-2 match the one-chip phase within "
              "5e-2 relative")
    _, first_calls["mesh/bert_base dp=2,fsdp=2"] = train_phase(
        "mesh/bert_base", _bert_base, sz, mesh_spec="dp=2,fsdp=2", n_dev=4)
    say(f"  transformer_lm.flash_fallback_count() seen: "
        f"{tlm.flash_fallback_count()}")


# ---------------------------------------------------------------------------
# kernel/flash
# ---------------------------------------------------------------------------
def _einsum_attention(q, k, v, causal):
    import jax
    import jax.numpy as jnp

    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / (q.shape[-1] ** .5)
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)


def _close(a, b, tol):
    import numpy as onp

    a = onp.asarray(a, onp.float32)
    b = onp.asarray(b, onp.float32)
    err = float(onp.max(onp.abs(a - b)))
    scale = max(1.0, float(onp.max(onp.abs(b))))
    return err <= tol * scale, err / scale


def flash_phase(sz, rehearse):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer_lm as tlm
    from mxnet_tpu.ops import pallas_kernels as pk
    import __graft_entry__ as graft

    say("== kernel/flash")
    fb0 = tlm.flash_fallback_count()
    # the driver's own entry, jitted as the driver does it.  On the CPU
    # auto mode picks the einsum, so the rehearsal asks for flash
    fn, args = graft.entry(use_flash_attention=True if rehearse else None,
                           num_layers=sz["entry_layers"])
    t_c = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    say(f"  __graft_entry__.entry() compiled in "
        f"{time.perf_counter() - t_c:.1f} s")
    if not rehearse:
        check("tpu_custom_call" in compiled.as_text(),
              "entry(): the Mosaic custom call (tpu_custom_call) is in the "
              "compiled text")
    logits = compiled(*args)
    ref_fn, ref_args = graft.entry(use_flash_attention=False,
                                   num_layers=sz["entry_layers"])
    ref = jax.jit(ref_fn)(*ref_args)
    ok, err = _close(logits, ref, 5e-2)
    check(ok and bool(jnp.isfinite(logits).all()),
          f"entry() logits {tuple(logits.shape)} finite and equal to the "
          f"use_flash_attention=False run to bf16 tolerance "
          f"(max err / scale {err:.2e})")

    for shape in sz["flash_shapes"]:
        for causal in (False, True):
            ks = jax.random.split(jax.random.PRNGKey(shape[1]), 4)
            q, k, v, w = (jax.random.normal(kk, shape, jnp.float32)
                          .astype(jnp.bfloat16) for kk in ks)

            def loss(f, q, k, v):
                return (f(q, k, v).astype(jnp.float32)
                        * w.astype(jnp.float32)).sum()

            flash = jax.jit(jax.value_and_grad(
                lambda q, k, v: loss(
                    lambda *a: pk.flash_attention(*a, causal=causal),
                    q, k, v), argnums=(0, 1, 2)))
            refg = jax.jit(jax.value_and_grad(
                lambda q, k, v: loss(
                    lambda *a: _einsum_attention(*a, causal=causal),
                    q, k, v), argnums=(0, 1, 2)))
            _, gf = flash(q, k, v)
            _, gr = refg(q, k, v)
            o_ok, o_err = _close(pk.flash_attention(q, k, v, causal=causal),
                                 _einsum_attention(q, k, v, causal), 3e-2)
            g = [_close(a, b, 3e-2) for a, b in zip(gf, gr)]
            check(o_ok and all(x[0] for x in g),
                  f"flash fwd+bwd {shape} causal={causal} vs fp32 einsum: "
                  f"out {o_err:.1e} dq {g[0][1]:.1e} dk {g[1][1]:.1e} "
                  f"dv {g[2][1]:.1e} (err / scale)")
    for shape in sz["cell_shapes"]:
        _flash_cell_shape(shape, timed=not rehearse)
    _keep_mask_everywhere(sz["cell_shapes"][0])
    check(tlm.flash_fallback_count() == fb0,
          "transformer_lm.flash_fallback_count() did not move")


def _keep_mask_everywhere(shape, width=768, keep_prob=0.9):
    """The dropout mask of a cell's hidden activations for one key: the
    default device's against the host CPU's, bit for bit."""
    import jax
    import numpy as onp

    from mxnet_tpu.ops.random import keep_mask

    key = onp.asarray(jax.random.key_data(jax.random.key(shape[0])))
    masks = [onp.asarray(keep_mask(jax.device_put(key, dev),
                                   (shape[1], shape[0], width), keep_prob))
             for dev in (jax.devices()[0], jax.devices("cpu")[0])]
    check(bool((masks[0] == masks[1]).all()),
          f"keep_mask {masks[0].shape} on {jax.devices()[0].platform} equals "
          f"the CPU's bit for bit (kept {masks[0].mean():.4f})")


def _flash_cell_shape(shape, timed, dropout_p=0.1, calls=20, heads=12):
    """A BERT cell's attention core at its own shape, the two paths of
    ``interleaved_selfatt`` over the (seq, batch, 12 * 3 * 64) projection
    with the probability dropout on.  The Pallas kernels against the dense
    float32 reference under the SAME mask, then (``timed``) forward +
    backward ms a call beside the unfused expression (bf16 products,
    float32 softmax).
    The arrays lie batch-major in memory and reach the operator through a
    transpose, as ``BERTSelfAttention`` hands them over."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import contrib
    from mxnet_tpu.ops import pallas_kernels as pk

    seq, bsz = shape
    d, bh = 64, shape[1] * heads
    key = jax.random.PRNGKey(seq)
    x, w = (jax.random.normal(kk, (bsz, seq, n * heads * d), jnp.float32)
            .astype(jnp.bfloat16)
            for kk, n in zip(jax.random.split(jax.random.PRNGKey(bsz)),
                             (3, 1)))

    def dense(qkv):
        q, k, v = (t.astype(jnp.float32)
                   for t in contrib._split_heads(qkv, heads))
        att = jax.nn.softmax(jnp.einsum(
            "bqd,bkd->bqk", q, k, precision=jax.lax.Precision.HIGHEST)
            / d ** 0.5, axis=-1)
        att = jnp.where(pk.dropout_keep_mask(key, bh, seq, seq, dropout_p),
                        att, 0.0) / (1.0 - dropout_p)
        return contrib._merge_heads(jnp.einsum(
            "bqk,bkd->bqd", att, v, precision=jax.lax.Precision.HIGHEST),
            bsz)

    def kernel(qkv):
        return pk.flash_attention_qkv(qkv, heads, dropout_p=dropout_p,
                                      dropout_key=key)

    def unfused(qkv):
        return contrib._unfused_selfatt(qkv, key, heads, dropout_p)

    def batch_major(f):
        return lambda x: f(x.transpose(1, 0, 2)).transpose(1, 0, 2)

    def with_grad(f):
        return jax.jit(jax.value_and_grad(
            lambda x: (batch_major(f)(x).astype(jnp.float32)
                       * w.astype(jnp.float32)).sum()))

    g_kernel, g_unfused = with_grad(kernel), with_grad(unfused)
    o_ok, o_err = _close(jax.jit(batch_major(kernel))(x),
                         jax.jit(batch_major(dense))(x), 3e-2)
    g_ok, g_err = _close(g_kernel(x)[1], with_grad(dense)(x)[1], 3e-2)
    check(o_ok and g_ok,
          f"flash_attention_qkv (seq, batch) {shape} dropout_p={dropout_p}, "
          f"{pk.qkv_heads_per_step(seq, heads, d)} heads a grid step, vs the "
          f"fp32 reference under the same mask: out {o_err:.1e} "
          f"dqkv {g_err:.1e} (err / scale)")
    if not timed:
        return

    def ms_a_call(fn):
        jax.block_until_ready(fn(x))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / calls * 1e3

    say(f"  {shape} forward + backward, ms a call over {calls}: Pallas "
        f"kernels {ms_a_call(g_kernel):.3f}, unfused expression "
        f"{ms_a_call(g_unfused):.3f}")


# ---------------------------------------------------------------------------
# serve/decode
# ---------------------------------------------------------------------------
# the engine runs at the default matmul precision (one bf16 pass on the MXU:
# 2^-9 per rounded operand), the oracle in float32: where the oracle's own top
# logits lie closer than this share of the largest |logit|, the engine may pick
# the other one.  Seen on the v5e: 1 of 39 tokens, at 1.9e-3 (PR 21)
TIE_TOL = 2 ** -6


def _worst_gap(model, params, prompt, out):
    """The engine's tokens against the oracle GIVEN THE ENGINE'S OWN PREFIX
    (``eager_generate``'s loop, teacher-forced): the largest amount, as a
    share of the largest |logit|, by which the oracle's logit for an engine
    token falls short of the oracle's maximum.  0.0 = every token is the
    oracle's argmax."""
    import jax.numpy as jnp

    worst = 0.0
    for i, tok in enumerate(out):
        toks = list(prompt) + out[:i]
        logits, _k, _v = model.prefill(params, jnp.asarray(toks, jnp.int32),
                                       len(toks))
        worst = max(worst, float((logits.max() - logits[tok])
                                 / jnp.abs(logits).max()))
    return worst


def decode_phase():
    import jax

    import mxnet_tpu as mx

    sd = mx.serving_decode
    on_tpu = jax.devices()[0].platform == "tpu"
    say("== serve/decode")
    say("  NOTE: TinyCausalLM at WIDTH 64 — the repo has no full-width "
        "DecodeModel yet (ROADMAP R1 brings one); this phase is here "
        "because the paged KV pool and its donation have never met a chip")
    say("  the engine runs at the default matmul precision, as a user's "
        "does; only the eager oracle is computed at 'highest'")
    model = sd.TinyCausalLM(vocab=64, d_model=64, n_layers=2, n_heads=4,
                            max_seq=64)
    params = model.init_params(seed=0)
    pool = sd.PagePool(pages=64, page=8)
    eng = sd.GenerativeEngine(model, params=params, pool=pool, max_rows=4,
                              name="smoke")
    t_c = time.perf_counter()
    n_prog = eng.warmup()
    say(f"  warmup: {n_prog} programs in {time.perf_counter() - t_c:.1f} s")
    ns = mx.program_store.namespace("serving_decode")
    tr0 = ns.traces
    geom = pool.register(model.n_layers, model.n_heads, model.head_dim)
    requests = [([3, 1, 4], 6), ([1, 5, 9, 2, 6], 5), ([5, 3], 6),
                (list(range(1, 18)), 4), ([8, 9, 7, 9, 3, 2, 3, 8, 4], 5),
                ([6], 6), ([2, 6, 4, 3, 3, 8, 3, 2, 7, 9, 5, 0, 2], 4),
                ([3, 1, 4], 3)]
    forks = mx.telemetry.get("prefix.cow_forks")
    for prompt, n_new in requests:
        k_old, v_old = pool.storage(geom)
        f0 = forks.value
        out = list(eng.generate(prompt, max_new_tokens=n_new))
        with jax.default_matmul_precision("highest"):    # the oracle only
            ref = list(sd.eager_generate(model, params, prompt, n_new))
            gap = 0.0 if out == ref else _worst_gap(model, params, prompt,
                                                    out)
        check(gap <= TIE_TOL,
              f"generate(len {len(prompt)}, +{n_new}) " + (
                  "token-exact vs eager_generate" if out == ref else
                  f"leaves eager_generate's {ref} only at a bf16 near tie "
                  f"(worst gap {gap:.1e} of the logit scale, {TIE_TOL:.1e} "
                  "allowed)") + f": {out}")
        if not on_tpu:
            continue
        if k_old.is_deleted() and v_old.is_deleted():
            say("  [ok]   the dispatch donated the previous pool buffers")
        else:
            # a whole-prompt prefix-cache hit skips the prefill: the first
            # pool operation is then PagePool.fork's EAGER
            # k.at[new].set(k[p]) — a copy of the whole pool, not a
            # donating dispatch — so the captured buffers stay alive
            check(forks.value > f0,
                  "  previous pool buffers not donated, but a prefix hit "
                  f"forked first ({forks.value - f0} eager full-pool "
                  "copy-on-write copies)")
    check(ns.traces - tr0 == 0, "0 retraces after warm-up")
    k, v = pool.storage(geom)
    want = {"tpu"} if on_tpu else {"cpu"}
    check(_platforms(k) == want and _platforms(v) == want,
          f"pool buffers live on {sorted(want)}")
    eng.close()


# ---------------------------------------------------------------------------
# --kernels: every public Pallas kernel once, against its jnp reference
# ---------------------------------------------------------------------------
def kernels_phase(sz):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    say("== kernels (every public kernel in pallas_kernels.__all__)")
    b = sz["kernel_batch"]
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    # BERT-base attention shape, forward only
    qa, ka, va = (jax.random.normal(k, (b * 12, 128, 64), jnp.float32)
                  .astype(jnp.bfloat16) for k in keys)
    ok, err = _close(
        jax.jit(lambda *a: pk.flash_attention(*a, causal=False))(qa, ka, va),
        _einsum_attention(qa, ka, va, False), 3e-2)
    check(ok, f"flash_attention: {err:.1e} (err / scale)")
    # the in-place kernels over the interleaved projection, dropout inside,
    # forward and backward against the dense reference under
    # dropout_keep_mask's mask, qkv_heads_per_step heads a grid step
    _flash_cell_shape((128, b), timed=False)
    covered = {"flash_attention", "flash_attention_qkv",
               "qkv_heads_per_step", "dropout_keep_mask"}
    missing = sorted(set(pk.__all__) - covered)
    check(not missing, f"every name in pallas_kernels.__all__ was exercised"
                       f" (missing: {missing})")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    global _PREFIX
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, CPU permitted, never prints the pass "
                         "line")
    ap.add_argument("--chips", type=int, default=None,
                    help="require this many devices; 4 or more makes the "
                         "mesh phase mandatory")
    ap.add_argument("--kernels", action="store_true",
                    help="compile every public Pallas kernel once against "
                         "its jnp reference (instead of the phases)")
    args = ap.parse_args(argv)
    if args.rehearse:
        _PREFIX = "REHEARSAL "
    t_start = time.perf_counter()

    import jax

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {n_dev} device(s), "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
              f"Nothing was run.  (--rehearse runs toy sizes on the CPU.)",
              file=sys.stderr)
        return 2
    if args.chips is not None and n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} but only {n_dev} "
              f"device(s) visible", file=sys.stderr)
        return 2

    import jaxlib
    import mxnet_tpu as mx
    from mxnet_tpu import native

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    cache = mx.program_store.enable_persistent_cache()
    say(f"platform: {dev.platform}   device_kind: {dev.device_kind}   "
        f"devices: {n_dev}")
    say(f"jax {jax.__version__}   jaxlib {jaxlib.__version__}   "
        f"libtpu {libtpu_version}   "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    say(f"compile cache: {cache}  "
        f"({'from JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'program_store default'}; "
        f"{len(os.listdir(cache)) if os.path.isdir(cache) else 0} entries "
        "at start)")
    say("native engine: " + (
        "active (libmxnet_tpu_native.so)" if native.available()
        else f"PYTHON FALLBACK ({native.build_error()})"))
    say(f"default context: {mx.current_context()}")

    sz = TOY if args.rehearse else REAL
    first_calls = {}
    if args.kernels:
        phases = "kernels"
        kernels_phase(sz)
    else:
        mesh = n_dev >= 4
        phases = ", ".join(PHASES if mesh else PHASES[:-1])
        if not mesh:
            say(f"mesh phase not run: {n_dev} device(s) visible "
                "(it runs with 4 or more; --chips 4 makes it mandatory)")
        mx.amp.init("bfloat16")
        rn_losses, first_calls["train/resnet50"] = train_phase(
            "train/resnet50", _resnet50, sz)
        _, first_calls["train/bert_base"] = train_phase(
            "train/bert_base", _bert_base, sz)
        flash_phase(sz, args.rehearse)
        decode_phase()
        if mesh:
            mesh_phase(sz, args.rehearse, rn_losses, first_calls)

    fallbacks = {ns.name: ns.aot_fallbacks
                 for ns in mx.program_store.NAMESPACES.values()
                 if ns.compile_count}
    check(not any(fallbacks.values()),
          "0 AOT fallbacks: no Program dropped from its compiled executable "
          f"to the retracing jit, per namespace {fallbacks}")
    disk = mx.program_store.disk_stats()
    say("== set-up cost (seconds; not a rate)")
    for name, s in first_calls.items():
        say(f"  first call {name}: {s:.1f}")
    say(f"  program_store.compile_seconds: "
        f"{mx.program_store.compile_seconds():.1f}   persistent cache: "
        f"{disk['hits']} hits, {disk['misses']} misses, dir {disk['dir']}")
    say(f"  wall: {time.perf_counter() - t_start:.0f} s   phases: {phases}")
    if args.rehearse:
        say("done: every rehearsed phase passed (a rehearsal never prints "
            "the pass line)")
        return 0
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind, "count": n_dev}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
