#!/usr/bin/env python
"""The two kernel paths the Granite-4.0-H cell runs, on the chip: the Mamba-2
scan at one group of 32 heads in chunks of 256 (in blocks of heads a grid
step, ``pallas_kernels.ssd_head_block``) beside Nemotron-H's 8 groups of 8
heads in chunks of 128, and the grouped causal core at heads of 64 (16 query
heads over 4, head-major blocks).  Each against XLA's form in float32 at the
highest precision on the same rounded operands, and each form's
milliseconds a call, forward and differentiated, in bf16, on a machine
with a TPU:

    python3 tools/granite_kernels_check.py

``--blocks 4 16 32`` times the scan at other heads a grid step as well (32
is the whole group: Mosaic's scoped VMEM refuses its backward).
``--rehearse`` runs toy shapes (on the CPU: under the interpreter, where the
times mean nothing).  The last line of stdout is the JSON, also written to
``chiprun_out/granite_kernels_check.json`` (a git-ignored directory).
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (seq, heads, groups, head_dim, state, chunk)
SCAN_SHAPES = {"granite_h_micro_train_s8k": (8192, 32, 1, 64, 128, 256),
               "nemotron_h_train_s8k": (8192, 64, 8, 64, 128, 128)}
TOY_SCAN = {"toy": (512, 32, 1, 64, 128, 256)}
# name: (seq, heads, kv_heads, head_dim, scale)
CORE_SHAPES = {"granite_h_micro_train_s8k": (8192, 16, 4, 64, 1 / 64)}
TOY_CORE = {"toy": (64, 4, 2, 64, 1 / 64)}
SCAN_NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
CORE_NAMES = ("o", "dq", "dk", "dv")


def _ms(fn, iters):
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _errors(names, got, want):
    import jax.numpy as jnp

    return {n: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                     / jnp.max(jnp.abs(w)))
            for n, g, w in zip(names, got, want)}


def _forms(fn, args, ct):
    """``(forward, value and every gradient)`` of ``fn`` as jitted
    functions, the cotangent ``ct``."""
    import jax
    import jax.numpy as jnp

    def both(*a):
        out, vjp = jax.vjp(fn, *a)
        return (out,) + vjp(ct.astype(out.dtype))

    grad = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                       * ct.astype(jnp.float32)),
                    argnums=tuple(range(len(args))))
    return jax.jit(fn), jax.jit(both), jax.jit(grad)


def _timed(fn, args, ct, names, want, iters):
    import jax.numpy as jnp

    forward, both, grad = _forms(fn, args, ct)
    got = both(*args)
    return {"error_over_scale": _errors(names, got, want),
            "finite": all(bool(jnp.all(jnp.isfinite(t.astype(jnp.float32))))
                          for t in got),
            "forward_ms": _ms(lambda: forward(*args), iters),
            "grad_ms": _ms(lambda: grad(*args), iters)}


def check_scan(shape, seed, iters, blocks):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops import ssm

    seq, h, g, p, n, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = jnp.bfloat16
    args = (jax.random.normal(ks[0], (1, seq, h, p), bf),
            jax.nn.softplus(jax.random.normal(ks[1], (1, seq, h)) - 3.0),
            -jnp.arange(1, h + 1, dtype=jnp.float32),
            (0.3 * jax.random.normal(ks[2], (1, seq, g, n))).astype(bf),
            (0.3 * jax.random.normal(ks[3], (1, seq, g, n))).astype(bf),
            jax.random.normal(ks[4], (h,)))
    ct = jax.random.normal(ks[5], (1, seq, h, p), bf)

    def scan(fused):
        return functools.partial(ssm._ssd_scan, chunk_size=chunk,
                                 fused=fused)

    with jax.default_matmul_precision("highest"):
        want = _forms(scan(False), args, ct)[1](
            *(t.astype(jnp.float32) for t in args))
    result = {"shape": shape,
              "heads_a_block": pk.ssd_head_block(h // g, p, chunk)}
    for name, fused in (("xla", False), ("kernels", True)):
        result[name] = _timed(scan(fused), args, ct, SCAN_NAMES, want, iters)
    was = pk._SSD_BLOCK
    for e in blocks:
        if (h // g) % e or e == result["heads_a_block"]:
            continue
        pk._SSD_BLOCK = e * p * chunk
        jax.clear_caches()
        try:
            result[f"kernels_{e}_heads"] = _timed(
                scan(True), args, ct, SCAN_NAMES, want, iters)
        except Exception as exc:            # Mosaic refused this block
            result[f"kernels_{e}_heads"] = {"error": str(exc)[:400]}
        finally:
            pk._SSD_BLOCK = was
            jax.clear_caches()
    return result


def check_core(shape, seed, iters):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import contrib
    from mxnet_tpu.ops import pallas_kernels as pk

    seq, heads, kv_heads, d, scale = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    args = (jax.random.normal(ks[0], (1, seq, heads * d), bf),
            jax.random.normal(ks[1], (1, seq, kv_heads * d), bf),
            jax.random.normal(ks[2], (1, seq, kv_heads * d), bf))
    ct = jax.random.normal(ks[3], (1, seq, heads * d), bf)

    def unfused(*a):
        return contrib._unfused_causal_gqa(*a, heads, kv_heads, scale)

    def kernels(*a):
        return pk.flash_attention_gqa(*a, heads, kv_heads, scale)

    with jax.default_matmul_precision("highest"):
        want = _forms(unfused, args, ct)[1](
            *(t.astype(jnp.float32) for t in args))
    return {"shape": shape,
            "xla": _timed(unfused, args, ct, CORE_NAMES, want, iters),
            "kernels": _timed(kernels, args, ct, CORE_NAMES, want, iters)}


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--blocks", type=int, nargs="*", default=[])
    args = parser.parse_args()

    import jax

    iters = 1 if args.rehearse else args.iters
    device = jax.devices()[0]
    report = {
        "device": device.device_kind, "platform": device.platform,
        "rehearsal": args.rehearse,
        "scan": {name: check_scan(shape, args.seed, iters, args.blocks)
                 for name, shape in (TOY_SCAN if args.rehearse
                                     else SCAN_SHAPES).items()},
        "core": {name: check_core(shape, args.seed, iters)
                 for name, shape in (TOY_CORE if args.rehearse
                                     else CORE_SHAPES).items()}}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/granite_kernels_check.json", "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
