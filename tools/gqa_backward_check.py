#!/usr/bin/env python
"""The grouped causal core's backward on the chip: the fused kernel's dq, dk
and dv against the two-kernel form's, and each form's milliseconds a call.

Only Mosaic has the write-back rule the fused kernel is built around (an
output block is written back when its index changes and is not read back on
a later visit; the interpreter reads it back), so the comparison that
counts is this one, at the shapes the two decoder cells run, in bf16:

    chiprun -- python3 tools/gqa_backward_check.py

``--rehearse`` runs toy shapes (on the CPU: under the interpreter, where
the times mean nothing).  The fused kernel runs at the block the program
gives it (``_gqa_bwd_block``); ``--blocks`` adds others.  The last line of stdout is the JSON, also written to
``chiprun_out/gqa_backward_check.json``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (seq, query heads, key-value heads, head_dim)
CELL_SHAPES = {
    "glm47_flash_train_s8k": (8192, 20, 20, 256),
    "nemotron_h_train_s8k": (8192, 32, 2, 128),
}
TOY_SHAPES = {"group_of_one": (96, 2, 2, 128), "grouped": (96, 4, 2, 128)}


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

    seq, heads, kv_heads, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(key, (1, seq, heads * d), jnp.bfloat16)
             for key in keys[:2])
    k, v = (jax.random.normal(key, (1, seq, kv_heads * d), jnp.bfloat16)
            for key in keys[2:])
    scale, block = d ** -0.5, pk.gqa_block(seq)
    out, lse = pk._gqa_forward(q, k, v, heads, kv_heads, scale, block)

    def backward(block, fused):
        return pk._gqa_backward(q, k, v, out, lse, do, heads, kv_heads,
                                scale, block, fused)

    fused_block = pk._gqa_bwd_block(seq, block)
    resident = pk._gqa_bwd_resident(seq, d, heads // kv_heads, fused_block)
    result = {"shape": shape, "block": block, "fused_block": fused_block,
              "resident_bytes": resident,
              "fits": resident <= pk._GQA_BWD_VMEM}
    split = backward(block, False)
    for b in sorted({fused_block, *blocks}):
        if seq % b:
            continue
        try:
            fused = backward(b, True)
        except Exception as exc:            # Mosaic refused this block
            result[f"fused_{b}"] = {"error": str(exc)[:400]}
            continue
        # the two forms differ by the order of float32 additions and by one
        # bf16 rounding: compare on the gradient's own scale
        result[f"fused_{b}"] = {
            name: {"max_abs_diff": float(jnp.max(jnp.abs(
                       f.astype(jnp.float32) - s.astype(jnp.float32)))),
                   "max_abs": float(jnp.max(jnp.abs(s.astype(jnp.float32)))),
                   "finite": bool(jnp.all(jnp.isfinite(
                       f.astype(jnp.float32))))}
            for name, f, s in zip(("dq", "dk", "dv"), fused, split)}
        result[f"fused_{b}"]["ms"] = _ms(lambda: backward(b, True), iters)
    result["split_ms"] = _ms(lambda: backward(block, False), iters)
    result["forward_ms"] = _ms(
        lambda: pk._gqa_forward(q, k, v, heads, kv_heads, scale, block),
        iters)
    return result


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--blocks", type=int, nargs="*", default=[])
    args = parser.parse_args()

    import jax

    from mxnet_tpu.ops import pallas_kernels as pk

    if args.rehearse:
        pk._BLOCK = 32
    shapes = TOY_SHAPES if args.rehearse else CELL_SHAPES
    device = jax.devices()[0]
    report = {"device": device.device_kind, "platform": device.platform,
              "rehearsal": args.rehearse,
              "shapes": {name: check(shape, args.seed,
                                     2 if args.rehearse else args.iters,
                                     args.blocks)
                         for name, shape in shapes.items()}}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gqa_backward_check.json", "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
