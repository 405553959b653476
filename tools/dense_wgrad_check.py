#!/usr/bin/env python
"""The dense product's weight gradient with its operands fused or written
once, on the chip: one layer's training step at every cell's dense shapes,
with the elementwise producers the cells put on each side of the product,
timed four ways (neither operand behind a barrier, the activation, the
output's gradient, both), and the rule ``ops/nn.py`` takes at each site.

A site is the layer ``y = x @ W.T`` between two producers: the one that
makes ``x`` from its inputs (a norm with the residual added, SwiGLU's
product, ...) and the one that turns ``y`` into the loss, whose backward
makes the output's gradient (the norm behind the layer, SwiGLU's gradient,
the softmax gradient, ...).  A step differentiates the loss for the float32
master weight and for the producer's inputs (so the data gradient and the
producers' backward run as in a cell) and applies Adam's update, as the
cells' step programs fuse it, in bf16 operands:

    python3 tools/dense_wgrad_check.py      # on one chip

All 24 sites compile 96 steps: about 20 minutes on one v5e; ``--sites``
names a subset.  ``--rehearse`` runs toy shapes (on the CPU the times mean
nothing).  The last line of stdout is the JSON, also written to the file
``--out`` names, if it names one.  Each form's updated weight and input
gradients are compared with the fused form's to the bit
(``equal_to_fused``): the products are the same, but on the TPU another
tiling may add their float32 partial sums in another order.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (tokens, in_units, num_hidden, the activation's producer, the
# producer of the output's gradient), as the cells' layers run them
SITES = {
    "olmo_hybrid_train_s8k.gate_up_proj":
        (8192, 3840, 22016, "post_norm_residual", "swiglu"),
    "olmo_hybrid_train_s8k.down_proj":
        (8192, 11008, 3840, "swiglu", "post_norm"),
    "olmo_hybrid_train_s8k.o_proj":
        (8192, 2880, 3840, "gated_norm", "post_norm"),
    "olmo_hybrid_train_s8k.v_proj":
        (8192, 3840, 2880, "post_norm_residual", "plain"),
    "olmo_hybrid_train_s8k.q_proj":
        (8192, 3840, 1440, "post_norm_residual", "plain"),
    "olmo_hybrid_train_s8k.lm_head":
        (8192, 3840, 12544, "rms_norm", "softmax_ce"),
    "granite_h_micro_train_s8k.gate_up_proj":
        (16384, 2048, 16384, "rms_norm", "swiglu"),
    "granite_h_micro_train_s8k.down_proj":
        (16384, 8192, 2048, "swiglu", "plain"),
    "granite_h_micro_train_s8k.in_proj":
        (16384, 2048, 4384, "rms_norm", "plain"),
    "granite_h_micro_train_s8k.out_proj":
        (16384, 2048, 2048, "gated_norm", "plain"),
    "granite_h_micro_train_s8k.lm_head":
        (16384, 2048, 12544, "rms_norm", "softmax_ce"),
    "nemotron_h_train_s8k.shared_up":
        (8192, 2688, 3712, "rms_norm", "relu2"),
    "nemotron_h_train_s8k.shared_down":
        (8192, 3712, 2688, "relu2", "plain"),
    "nemotron_h_train_s8k.in_proj":
        (8192, 2688, 10304, "rms_norm", "plain"),
    "nemotron_h_train_s8k.out_proj":
        (8192, 4096, 2688, "gated_norm", "plain"),
    "nemotron_h_train_s8k.lm_head":
        (8192, 2688, 16384, "rms_norm", "softmax_ce"),
    "glm47_flash_train_s8k.gate_up_proj":
        (8192, 2048, 20480, "rms_norm", "swiglu"),
    "glm47_flash_train_s8k.down_proj":
        (8192, 10240, 2048, "swiglu", "plain"),
    "glm47_flash_train_s8k.shared_gate_up":
        (8192, 2048, 3072, "rms_norm", "swiglu"),
    "glm47_flash_train_s8k.lm_head":
        (8192, 2048, 19360, "rms_norm", "softmax_ce"),
    "bert_base.qkv": (16384, 768, 2304, "layer_norm", "plain"),
    "bert_base.ffn_1": (16384, 768, 3072, "layer_norm", "gelu"),
    "bert_base.ffn_2": (16384, 3072, 768, "gelu", "plain"),
    "bert_base.decoder": (16384, 768, 30528, "layer_norm", "softmax_ce"),
}
TOY_SITES = {"toy.gate_up_proj": (64, 32, 96, "post_norm_residual", "swiglu"),
             "toy.down_proj": (64, 48, 32, "swiglu", "post_norm"),
             "toy.lm_head": (64, 32, 80, "rms_norm", "softmax_ce"),
             "toy.ffn_1": (64, 16, 32, "layer_norm", "gelu"),
             "toy.shared_up": (64, 16, 24, "gated_norm", "relu2")}
FORMS = {"fused": (False, False), "activation": (True, False),
         "gradient": (False, True), "both": (True, True)}


def _ms(step, state, inputs, iters):
    import jax

    state = jax.block_until_ready(step(state, inputs)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        state = step(state, inputs)[0]
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / iters * 1e3, state


def _site(shape, seed):
    """``(inputs, loss(weight, inputs, barrier_x, barrier_dy))``: the
    producers' inputs drawn from ``seed``, bf16 where a cell's are."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn, ssm

    tokens, in_units, num_hidden, x_kind, dy_kind = shape
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def normal(cols, dtype=bf16):
        return jax.random.normal(next(keys), (tokens, cols), dtype)

    def scale(cols):
        return jax.random.uniform(next(keys), (cols,), f32, 0.5, 1.5)

    def silu(t):
        return t * jax.nn.sigmoid(t)

    # the activation's producer: inputs, and x from them
    x_in = {"post_norm_residual": lambda: (normal(in_units),
                                           normal(in_units)),
            "rms_norm": lambda: (normal(in_units),),
            "layer_norm": lambda: (normal(in_units, f32),),
            "swiglu": lambda: (normal(2 * in_units),),
            "relu2": lambda: (normal(in_units),),
            "gelu": lambda: (normal(in_units),),
            "gated_norm": lambda: (normal(in_units), normal(in_units)),
            "plain": lambda: (normal(in_units),)}[x_kind]()
    gamma, beta = scale(in_units), scale(in_units) - 1.0

    def produce(ins):
        if x_kind == "post_norm_residual":
            return ins[0] + ssm.rms_norm(ins[1], gamma)
        if x_kind == "rms_norm":
            return ssm.rms_norm(ins[0], gamma)
        if x_kind == "layer_norm":
            return nn.layer_norm_op(ins[0], gamma, beta).astype(bf16)
        if x_kind == "swiglu":
            gate, up = jnp.split(ins[0], 2, axis=-1)
            return silu(gate) * up
        if x_kind == "relu2":
            return jnp.square(jax.nn.relu(ins[0]))
        if x_kind == "gelu":
            return jax.nn.gelu(ins[0] + beta.astype(bf16), approximate=False)
        if x_kind == "gated_norm":
            return ssm.gated_rms_norm(ins[0], ins[1], gamma)
        return ins[0]

    # what turns y into the loss: its backward makes the output's gradient
    out_scale = scale(num_hidden)
    width = num_hidden // 2 if dy_kind == "swiglu" else num_hidden
    target = (jax.random.randint(next(keys), (tokens,), 0, num_hidden)
              if dy_kind == "softmax_ce" else normal(width))

    def consume(y):
        if dy_kind == "softmax_ce":
            return jnp.sum(nn.sparse_softmax_cross_entropy(y, target))
        if dy_kind == "post_norm":
            y = ssm.rms_norm(y, out_scale)
        elif dy_kind == "swiglu":
            gate, up = jnp.split(y, 2, axis=-1)
            y = silu(gate) * up
        elif dy_kind == "relu2":
            y = jnp.square(jax.nn.relu(y))
        elif dy_kind == "gelu":
            y = jax.nn.gelu(y + out_scale.astype(bf16), approximate=False)
        return jnp.sum(y.astype(f32) * target.astype(f32))

    def loss(weight, ins, barrier_x, barrier_dy):
        y = nn.dense_product(produce(ins), weight.astype(bf16), barrier_x,
                             barrier_dy)
        return consume(y) / tokens

    return x_in, loss


def check(shape, seed, iters):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn

    tokens, in_units, num_hidden = shape[:3]
    inputs, loss = _site(shape, seed)
    weight = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (num_hidden, in_units), jnp.float32) \
        * in_units ** -0.5
    b1, b2, lr, eps = 0.9, 0.999, 1e-4, 1e-8

    def make_step(barrier_x, barrier_dy):
        grad = jax.grad(loss, argnums=(0, 1))

        def step(state, ins):
            w, m, v = state
            dw, dins = grad(w, ins, barrier_x, barrier_dy)
            m = b1 * m + (1 - b1) * dw
            v = b2 * v + (1 - b2) * jnp.square(dw)
            return (w - lr * m / (jnp.sqrt(v) + eps), m, v), dins
        return jax.jit(step, donate_argnums=(0,))

    def fresh():
        return (weight + 0, jnp.zeros_like(weight), jnp.zeros_like(weight))

    rule = nn._wgrad_sides((num_hidden, in_units))
    result = {"shape": list(shape), "rule": next(
        name for name, form in FORMS.items() if form == rule),
        "tiles": [-(-w // nn.WGRAD_TILE) for w in (in_units, num_hidden)]}
    first = None
    for name, form in FORMS.items():
        step = make_step(*form)
        (w, _, _), dins = step(fresh(), inputs)
        got = [w] + list(dins)
        if first is None:
            first = got
        ms, _ = _ms(step, fresh(), inputs, iters)
        result[name] = {"ms": ms, "equal_to_fused": all(
            bool(jnp.array_equal(a, b)) for a, b in zip(got, first))}
    macs = 3 * tokens * in_units * num_hidden
    result["product_ms_at_peak"] = 2 * macs / 197e12 * 1e3
    result["best"] = min(FORMS, key=lambda name: result[name]["ms"])
    return result


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--sites", nargs="*", default=None)
    parser.add_argument("--out", help="also write the JSON report here")
    args = parser.parse_args()

    import jax

    from mxnet_tpu.ops import nn

    sites = TOY_SITES if args.rehearse else SITES
    if args.sites:
        sites = {name: sites[name] for name in args.sites}
    device = jax.devices()[0]
    report = {"device": device.device_kind, "platform": device.platform,
              "rehearsal": args.rehearse,
              "tile": nn.WGRAD_TILE, "min_tiles": nn.WGRAD_MIN_TILES,
              "sites": {}}
    for name, shape in sites.items():
        report["sites"][name] = check(shape, args.seed,
                                      1 if args.rehearse else args.iters)
        row = report["sites"][name]
        print(name, row["rule"], row["best"], " ".join(
            f"{form}={row[form]['ms']:.3f}" for form in FORMS),
            file=sys.stderr, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
