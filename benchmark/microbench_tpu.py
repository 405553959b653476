"""Targeted TPU microbenchmarks behind docs/PERF.md's roofline analysis.

    python benchmark/microbench_tpu.py [--which all|dot|conv|bn|int8|
                                               fused|epilogue]

Measures, with the bench fencing discipline (warm + host read, fenced
timed region):
  - dot:      8192^3 matmul, bf16 vs s8xs8->s32 (does int8 hit the 2x MXU?)
  - conv:     a resnet-core conv chain, bf16 NHWC vs int8 NHWC, with the
              requantize epilogue on/off (where does the int8 lane lose?)
  - bn:       conv chain with batch-stat BatchNorm vs without (what do the
              stats reductions + normalize passes cost the train step?)
  - fused:    the round-5 matmul+BN-stats producer kernel vs XLA
  - epilogue: the round-9 fused conv/BN/ReLU EPILOGUE pair (stats-only
              pass + in-register scale-shift/residual/relu) vs XLA — the
              MXNET_FUSED_EPILOGUE decision bench
  - int8:     the rebuilt fused int8 matmul vs lax s8 dot (+ requantize
              rows) — the MXNET_INT8_PALLAS re-entry bench

Each result prints one line: name, ms/iter, TFLOP/s (or TOP/s), ratio
to the section's baseline.  Keep runs short: the chip budget matters
more than tight confidence intervals.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import jax
import jax.numpy as jnp
import numpy as onp


def timeit(fn, *args, iters=20, warm=3):
    out = fn(*args)
    jax.block_until_ready(out)
    for _ in range(warm):
        out = fn(*args)
    _ = float(jnp.asarray(out).ravel()[0].astype(jnp.float32))  # drain
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _ = float(jnp.asarray(out).ravel()[0].astype(jnp.float32))  # fence
    return (time.perf_counter() - t0) / iters


def section_dot():
    n = 8192
    flops = 2 * n ** 3
    key = jax.random.PRNGKey(0)
    a16 = jax.random.normal(key, (n, n), jnp.bfloat16)
    b16 = jax.random.normal(key, (n, n), jnp.bfloat16)

    f_bf16 = jax.jit(lambda a, b: (a @ b).sum())
    dt = timeit(f_bf16, a16, b16)
    base = flops / dt / 1e12
    print(f"dot bf16 {n}^3: {dt*1e3:8.2f} ms  {base:6.1f} TFLOP/s  1.00x")

    a8 = (jax.random.normal(key, (n, n)) * 10).astype(jnp.int8)
    b8 = (jax.random.normal(key, (n, n)) * 10).astype(jnp.int8)
    f_s8 = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).sum())
    dt = timeit(f_s8, a8, b8)
    tops = flops / dt / 1e12
    print(f"dot s8s8s32 {n}^3: {dt*1e3:6.2f} ms  {tops:6.1f} TOP/s   "
          f"{tops/base:.2f}x vs bf16")


def _mkconv(dtype, epilogue):
    """One resnet-core 3x3 conv (NHWC), optionally with the int8 lane's
    requantize epilogue shape."""
    dn = jax.lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                        ("NHWC", "OHWI", "NHWC"))

    def f(x, w):
        out = jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
            preferred_element_type=jnp.int32 if dtype == jnp.int8
            else jnp.float32)
        if epilogue == "requant":
            out = out.astype(jnp.float32) * 0.01
            out = jnp.maximum(out, 0)
            out = jnp.clip(jnp.round(out * 31.0), -127, 127).astype(jnp.int8)
        elif epilogue == "relu":
            out = jnp.maximum(out, 0).astype(dtype)
        return out

    return jax.jit(lambda x, w: f(x, w).astype(jnp.int32).sum())


def section_conv():
    # resnet stage-3 texture: bs64 (the int8 lane), 28x28x256 -> 256
    key = jax.random.PRNGKey(1)
    shape_x, shape_w = (64, 28, 28, 256), (256, 3, 3, 256)
    flops = 2 * 64 * 28 * 28 * 256 * 3 * 3 * 256
    x16 = jax.random.normal(key, shape_x, jnp.bfloat16)
    w16 = jax.random.normal(key, shape_w, jnp.bfloat16)
    dt = timeit(_mkconv(jnp.bfloat16, "relu"), x16, w16)
    base = flops / dt / 1e12
    print(f"conv bf16+relu: {dt*1e3:8.2f} ms  {base:6.1f} TFLOP/s  1.00x")

    x8 = (jax.random.normal(key, shape_x) * 10).astype(jnp.int8)
    w8 = (jax.random.normal(key, shape_w) * 10).astype(jnp.int8)
    for epi in ("none", "requant"):
        dt = timeit(_mkconv(jnp.int8, epi), x8, w8)
        tops = flops / dt / 1e12
        print(f"conv s8 epi={epi:<8}: {dt*1e3:6.2f} ms  {tops:6.1f} TOP/s"
              f"   {tops/base:.2f}x vs bf16")


def section_bn():
    # 4-deep conv chain, with vs without batch-stat BN between convs —
    # the delta is what BN costs the bf16 train step's forward texture
    key = jax.random.PRNGKey(2)
    bs = 128
    x = jax.random.normal(key, (bs, 28, 28, 256), jnp.bfloat16)
    ws = [jax.random.normal(jax.random.PRNGKey(i), (256, 3, 3, 256),
                            jnp.bfloat16) for i in range(4)]
    dn = jax.lax.conv_dimension_numbers(x.shape, ws[0].shape,
                                        ("NHWC", "OHWI", "NHWC"))
    flops = 4 * 2 * bs * 28 * 28 * 256 * 3 * 3 * 256

    def chain(x, ws, use_bn):
        for w in ws:
            x = jax.lax.conv_general_dilated(
                x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn)
            if use_bn:
                x32 = x.astype(jnp.float32)
                mean = jnp.mean(x32, axis=(0, 1, 2))
                var = jnp.maximum(
                    jnp.mean(x32 * x32, axis=(0, 1, 2)) - mean * mean, 0.0)
                sc = jax.lax.rsqrt(var + 1e-5)
                x = (x * sc.astype(x.dtype)
                     - (mean * sc).astype(x.dtype))
            x = jnp.maximum(x, 0)
        return x.astype(jnp.float32).sum()

    for use_bn in (False, True):
        f = jax.jit(lambda x, *ws: chain(x, ws, use_bn))
        dt = timeit(f, x, *ws, iters=10)
        tf = flops / dt / 1e12
        print(f"conv-chain bn={use_bn!s:<5}: {dt*1e3:7.2f} ms  "
              f"{tf:6.1f} TFLOP/s")


def section_fused_stats():
    # A/B: XLA matmul + separate stats reduction vs the Pallas fused
    # producer+stats kernel (ops/pallas_kernels.matmul_bn_stats) — the
    # resnet stage-2 1x1-conv texture at bs128 (M = 128*28*28)
    from mxnet_tpu.ops.pallas_kernels import matmul_bn_stats

    key = jax.random.PRNGKey(3)
    m, k, n = 128 * 28 * 28, 512, 128
    x = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.bfloat16)
    flops = 2 * m * k * n

    def xla_ref(x, w):
        y = jnp.maximum((x @ w), 0)
        y32 = y.astype(jnp.float32)
        return y, jnp.sum(y32, 0), jnp.sum(y32 * y32, 0)

    def fence_all(out):
        y, s, ss = out
        # keep ALL outputs live on both sides — otherwise XLA dead-code-
        # eliminates the unfenced reductions and the A/B measures
        # different work
        return y.astype(jnp.float32).sum() + s.sum() + ss.sum()

    f = jax.jit(lambda x, w: fence_all(xla_ref(x, w)))
    dt = timeit(f, x, w, iters=10)
    base = flops / dt / 1e12
    print(f"mm+stats XLA:    {dt*1e3:8.2f} ms  {base:6.1f} TFLOP/s  1.00x")

    g = jax.jit(lambda x, w: fence_all(matmul_bn_stats(x, w, relu=True)))
    dt = timeit(g, x, w, iters=10)
    tf = flops / dt / 1e12
    print(f"mm+stats pallas: {dt*1e3:8.2f} ms  {tf:6.1f} TFLOP/s  "
          f"{tf/base:.2f}x vs XLA")


def section_fused_epilogue():
    # The round-9 decision bench for MXNET_FUSED_EPILOGUE: the
    # bottleneck-final texture conv1x1 + train-BN + residual-add + relu
    # as (a) plain XLA (conv write + stats read + normalize read/write —
    # whatever XLA fuses of it) vs (b) the fused-epilogue pair
    # (matmul_stats + matmul_epilogue: ONE HBM pass over the conv
    # output at 2x matmul FLOPs).  If (b) wins on chip, the knob flips
    # to default 1 and bench.py ResNet lanes stamp fused_epilogue=true.
    from mxnet_tpu.ops.pallas_kernels import (fused_blocks, matmul_stats,
                                              matmul_epilogue)

    key = jax.random.PRNGKey(4)
    # resnet stage-3 bottleneck-final: bs128, 14x14, 256 -> 1024
    m, k, n = 128 * 14 * 14, 256, 1024
    flops = 2 * m * k * n
    x = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.bfloat16) * 0.05
    gamma = jnp.abs(jax.random.normal(key, (n,), jnp.float32)) + 0.5
    beta = jax.random.normal(key, (n,), jnp.float32)
    r = jax.random.normal(key, (m, n), jnp.bfloat16)
    blocks = fused_blocks(m, k, n)
    assert blocks is not None

    def xla_ref(x, w, gamma, beta, r):
        z = (x @ w).astype(jnp.float32)
        mean = jnp.mean(z, axis=0)
        var = jnp.maximum(jnp.mean(z * z, axis=0) - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + 1e-5)
        y = z * (inv * gamma) + (beta - mean * inv * gamma)
        out = jnp.maximum(y + r.astype(jnp.float32), 0.0)
        return out.astype(x.dtype)

    f = jax.jit(lambda *a: xla_ref(*a).astype(jnp.float32).sum())
    dt = timeit(f, x, w, gamma, beta, r, iters=10)
    base = flops / dt / 1e12
    print(f"c1x1+bn+add+relu XLA:    {dt*1e3:8.2f} ms  {base:6.1f} "
          f"TFLOP/s  1.00x")

    def fused(x, w, gamma, beta, r):
        s, ss = matmul_stats(x, w, **blocks)
        mean = s / m
        var = jnp.maximum(ss / m - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + 1e-5)
        sc = inv * gamma
        return matmul_epilogue(x, w, sc, beta - mean * sc, residual=r,
                               relu=True, **blocks)

    g = jax.jit(lambda *a: fused(*a).astype(jnp.float32).sum())
    dt = timeit(g, x, w, gamma, beta, r, iters=10)
    tf = flops / dt / 1e12        # model FLOPs; the fused path pays 2x
    print(f"c1x1+bn+add+relu fused:  {dt*1e3:8.2f} ms  {tf:6.1f} "
          f"TFLOP/s  {tf/base:.2f}x vs XLA (2x matmul FLOPs inside)")

    # inference texture: scale/shift known ahead — epilogue pass only
    sc = gamma * 0.3
    bi = beta
    fi = jax.jit(lambda x, w: jnp.maximum(
        (x @ w).astype(jnp.float32) * sc + bi, 0.0)
        .astype(jnp.float32).sum())
    dt = timeit(fi, x, w, iters=10)
    base_i = flops / dt / 1e12
    gi = jax.jit(lambda x, w: matmul_epilogue(x, w, sc, bi, relu=True,
                                              **blocks)
                 .astype(jnp.float32).sum())
    dt = timeit(gi, x, w, iters=10)
    tf = flops / dt / 1e12
    print(f"c1x1+scale+relu XLA:     {base_i:6.1f} TFLOP/s  1.00x | "
          f"epilogue kernel: {tf:6.1f} TFLOP/s  {tf/base_i:.2f}x")


def section_int8_pallas():
    # Round-9 re-measurement bench for the int8 verdict: the REBUILT
    # fused int8 matmul ((m,n,k) grid, s32 VMEM accumulator,
    # in-register requantize — ops/pallas_kernels.int8_matmul) vs lax
    # s8 dot, with the bf16 reference row.  The round-5 conv-level
    # kernels measured 0.345x of lax on chip (BENCH_builder_r05) and
    # were DELETED; MXNET_INT8_PALLAS refuses until THIS bench beats
    # lax on chip (contrib/quantization._INT8_PALLAS_VERDICT).
    from mxnet_tpu.ops.pallas_kernels import int8_blocks, int8_matmul

    key = jax.random.PRNGKey(5)
    # the 1x1-conv-as-matmul texture: bs32 28x28, 512 -> 128
    m, k, n = 32 * 28 * 28, 512, 128
    flops = 2 * m * k * n
    qx = jax.random.randint(key, (m, k), -127, 128, jnp.int8)
    qw = jax.random.randint(key, (k, n), -127, 128, jnp.int8)
    scale = 3e-4
    blocks = int8_blocks(m, k, n)
    assert blocks is not None

    def lax_s8(qx, qw):
        acc = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * scale).sum()

    f = jax.jit(lax_s8)
    dt = timeit(f, qx, qw, iters=10)
    base = flops / dt / 1e12
    print(f"mm s8 lax dot:    {dt*1e3:8.2f} ms  {base:6.1f} TOP/s  1.00x")

    g = jax.jit(lambda qx, qw: int8_matmul(qx, qw, scale, **blocks).sum())
    dt = timeit(g, qx, qw, iters=10)
    tf = flops / dt / 1e12
    print(f"mm s8 pallas:     {dt*1e3:8.2f} ms  {tf:6.1f} TOP/s  "
          f"{tf/base:.2f}x vs lax")

    # fused requantize epilogue row (the production int8 graph texture)
    def lax_rq(qx, qw):
        acc = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        out = jnp.maximum(acc.astype(jnp.float32) * scale, 0.0)
        return jnp.clip(jnp.round(out * 31.0), -127, 127) \
            .astype(jnp.int8).astype(jnp.int32).sum()

    f2 = jax.jit(lax_rq)
    dt = timeit(f2, qx, qw, iters=10)
    base2 = flops / dt / 1e12
    g2 = jax.jit(lambda qx, qw: int8_matmul(
        qx, qw, scale, relu=True, out_scale=31.0, **blocks)
        .astype(jnp.int32).sum())
    dt = timeit(g2, qx, qw, iters=10)
    tf = flops / dt / 1e12
    print(f"mm s8+requant lax {base2:6.1f} TOP/s 1.00x | pallas "
          f"{tf:6.1f} TOP/s {tf/base2:.2f}x")

    bx = (qx.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    bw = qw.astype(jnp.bfloat16)
    h2 = jax.jit(lambda x, w: (x @ w).astype(jnp.float32).sum())
    dt = timeit(h2, bx, bw, iters=10)
    tf = flops / dt / 1e12
    print(f"mm bf16 matmul:   {dt*1e3:8.2f} ms  {tf:6.1f} TFLOP/s  "
          f"{tf/base:.2f}x vs lax-s8")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", default="all",
                    choices=["all", "dot", "conv", "bn", "int8", "fused",
                             "epilogue"])
    args = ap.parse_args()
    from mxnet_tpu import program_store

    program_store.enable_persistent_cache(min_compile_secs=1)
    print(f"backend: {jax.default_backend()}  {jax.devices()}")
    if args.which in ("all", "dot", "int8"):
        section_dot()
    if args.which in ("all", "conv", "int8"):
        section_conv()
    if args.which in ("all", "bn"):
        section_bn()
    if args.which in ("all", "fused"):
        section_fused_stats()
    if args.which in ("all", "epilogue"):
        section_fused_epilogue()
    if args.which in ("all", "int8"):
        section_int8_pallas()


if __name__ == "__main__":
    main()
