#!/usr/bin/env python
"""The short causal depthwise convolution's Pallas kernel pair on the chip:
its outputs and the gradients of data, taps and bias against XLA's shifted
multiply-adds, both against XLA's form in float32, and each form's
milliseconds a call.

The kernels are tested under the interpreter on the CPU; what only Mosaic
decides (the direction of a lane rotation, the layout of a block) is checked
here, at the hybrid cells' shapes, in bf16:

    python3 tools/conv_check.py      # on one chip

``--rehearse`` runs toy shapes (on the CPU: under the interpreter, where
the times mean nothing).  ``--blocks`` times the kernels at other numbers of
channels a grid step as well.  The last line of stdout is the JSON, also
written to the file ``--out`` names, if it names one.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (batch, length, projection width, convolved channels from, cuts of
# the result, bias) as the mixers hand the convolution over
CELL_SHAPES = {
    "nemotron_h_train_s8k": (1, 8192, 10304, 4096, (4096, 1024, 1024), True),
    "granite_h_micro_train_s8k": (2, 8192, 4384, 2048, (2048, 128, 128),
                                  True),
    "olmo_hybrid_train_s8k.keys": (1, 8192, 1440, 0, (1440,), False),
    "olmo_hybrid_train_s8k.values": (1, 8192, 2880, 0, (2880,), False)}
TOY_SHAPES = {"toy": (2, 200, 88, 16, (32, 16, 16), True)}


def _ms(fn, iters):
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def check(shape, seed, iters, blocks):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops import ssm

    bsz, length, width, begin, splits, has_bias = shape
    channels = sum(splits)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (bsz, length, width), jnp.bfloat16)
    w = jax.random.uniform(ks[1], (channels, 4), minval=-0.5, maxval=0.5)
    b = jax.random.uniform(ks[2], (channels,), minval=-0.5, maxval=0.5) \
        if has_bias else None
    dys = [jax.random.normal(k, (bsz, length, c), jnp.bfloat16)
           for k, c in zip(jax.random.split(ks[3], len(splits)), splits)]
    wrt = (0, 1, 2) if has_bias else (0, 1)
    names = [f"y{i}" for i in range(len(splits))] + ["dx", "dw", "db"][
        :len(wrt)]

    def form(fused):
        fn = ssm._conv_fused if fused else ssm._conv_shifted
        return lambda x, w, b: fn(x, w, b, "silu", begin, splits)

    def both(fused, x):
        outs, vjp = jax.vjp(form(fused), x, w, b)
        return tuple(outs) + vjp(tuple(d.astype(o.dtype)
                                       for d, o in zip(dys, outs)))[:len(wrt)]

    # one jitted function a form, built once
    forward = {f: jax.jit(form(f)) for f in (False, True)}
    grads = {f: jax.jit(jax.grad(
        lambda x, w, b, f=f: sum(jnp.sum(o.astype(jnp.float32)
                                         * d.astype(jnp.float32))
                                 for o, d in zip(form(f)(x, w, b), dys)),
        argnums=wrt)) for f in (False, True)}
    differentiated = {f: jax.jit(lambda x, f=f: both(f, x))
                      for f in (False, True)}

    # the reference: XLA's form on the same (rounded) data in float32
    want = differentiated[False](x.astype(jnp.float32))
    scale = {n: float(jnp.max(jnp.abs(t))) for n, t in zip(names, want)}

    def errors(got):
        return {n: float(jnp.max(jnp.abs(g.astype(jnp.float32) - t)))
                / scale[n] for n, g, t in zip(names, got, want)}

    result = {"shape": shape, "scale": scale}
    for name, fused in (("xla", False), ("kernels", True)):
        got = differentiated[fused](x)
        result[name] = {
            "error_over_scale": errors(got),
            "finite": all(bool(jnp.all(jnp.isfinite(
                t.astype(jnp.float32)))) for t in got),
            "forward_ms": _ms(lambda: forward[fused](x, w, b), iters),
            "grad_ms": _ms(lambda: grads[fused](x, w, b), iters)}
    padded = length + (-length % 128)
    bounds = (channels, begin) + splits
    result["kernels"]["channel_block"] = pk.conv_channel_block(
        bounds, padded, x.dtype.itemsize)
    was = pk._CONV_BLOCK
    for block in blocks:
        if block == result["kernels"]["channel_block"]:
            continue
        pk._CONV_BLOCK = block * padded
        try:
            if pk.conv_channel_block(bounds, padded, 2) != block:
                continue
            jax.clear_caches()          # the block is read when traced
            result[f"kernels_{block}_channels"] = {
                "forward_ms": _ms(lambda: forward[True](x, w, b), iters),
                "grad_ms": _ms(lambda: grads[True](x, w, b), iters)}
        except Exception as exc:            # Mosaic refused this block
            result[f"kernels_{block}_channels"] = {"error": str(exc)[:400]}
        finally:
            pk._CONV_BLOCK = was
            jax.clear_caches()
    return result


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--blocks", type=int, nargs="*", default=[])
    parser.add_argument("--out", help="also write the JSON report here")
    args = parser.parse_args()

    import jax

    shapes = TOY_SHAPES if args.rehearse else CELL_SHAPES
    device = jax.devices()[0]
    report = {"device": device.device_kind, "platform": device.platform,
              "rehearsal": args.rehearse,
              "shapes": {name: check(shape, args.seed,
                                     1 if args.rehearse else args.iters,
                                     args.blocks)
                         for name, shape in shapes.items()}}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
