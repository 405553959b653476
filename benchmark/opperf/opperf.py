#!/usr/bin/env python
"""Per-operator micro-benchmark runner.

Reference analog: ``benchmark/opperf/opperf.py`` + op discovery in
``benchmark/opperf/utils/op_registry_utils.py`` — time every registered
operator's forward (and backward) for regression hunting.

Usage:
  python benchmark/opperf/opperf.py                 # representative set
  python benchmark/opperf/opperf.py --ops relu,dot  # specific ops
  python benchmark/opperf/opperf.py --all           # every auto-runnable op
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax  # noqa: E402
import numpy as onp  # noqa: E402

# ops that need structured attrs: name -> (input shapes, attrs)
_SPECIAL = {
    "FullyConnected": ([(64, 256), (512, 256), (512,)],
                       {"num_hidden": 512}),
    "Convolution": ([(8, 32, 28, 28), (64, 32, 3, 3), (64,)],
                    {"kernel": (3, 3), "num_filter": 64}),
    "Pooling": ([(8, 32, 28, 28)], {"kernel": (2, 2), "pool_type": "max",
                                    "stride": (2, 2)}),
    "softmax": ([(128, 1000)], {}),
    "log_softmax": ([(128, 1000)], {}),
    "dot": ([(512, 512), (512, 512)], {}),
    "batch_dot": ([(32, 128, 128), (32, 128, 128)], {}),
    "sum": ([(256, 1024)], {"axis": 1}),
    "mean": ([(256, 1024)], {"axis": 1}),
    "take": ([(1000, 128), (64,)], {}),
    "embedding": ([(64,), (1000, 128)], {"input_dim": 1000,
                                         "output_dim": 128}),
    "LayerNorm": ([(64, 768), (768,), (768,)], {}),
    "transpose": ([(256, 256)], {}),
    "reshape": ([(256, 256)], {"shape": (65536,)}),
}

_DEFAULT_SET = list(_SPECIAL) + [
    "relu", "sigmoid", "tanh", "exp", "log", "sqrt", "square", "abs",
    "broadcast_add", "broadcast_mul", "broadcast_div", "clip",
]


def _inputs_for(name, schema):
    import mxnet_tpu as mx

    if name in _SPECIAL:
        shapes, attrs = _SPECIAL[name]
        rng = onp.random.RandomState(0)
        arrays = []
        for i, s in enumerate(shapes):
            if name in ("take", "embedding") and i == (1 if name == "take"
                                                       else 0):
                arrays.append(mx.nd.array(
                    rng.randint(0, 100, s).astype(onp.int32)))
            else:
                arrays.append(mx.nd.array(rng.rand(*s).astype(onp.float32)
                                          + 0.1))
        return arrays, attrs
    rng = onp.random.RandomState(0)
    n = schema.num_inputs if schema.num_inputs > 0 else 1
    arrays = [mx.nd.array(rng.rand(256, 256).astype(onp.float32) + 0.1)
              for _ in range(n)]
    return arrays, {}


def bench_op(name, warmup=3, runs=20, with_backward=True):
    import mxnet_tpu as mx
    from mxnet_tpu.ops.registry import find_op

    schema = find_op(name)
    if schema is None:
        return {"op": name, "error": "not registered"}
    try:
        arrays, attrs = _inputs_for(name, schema)
        invoke = mx.nd.invoke

        def fwd():
            out = invoke(schema, arrays, dict(attrs))
            (out[0] if isinstance(out, list) else out).wait_to_read()
            return out

        for _ in range(warmup):
            fwd()
        t0 = time.perf_counter()
        for _ in range(runs):
            fwd()
        fwd_ms = (time.perf_counter() - t0) / runs * 1e3

        bwd_ms = None
        if with_backward and schema.differentiable:
            for a in arrays:
                if a.dtype.kind == "f":
                    a.attach_grad()

            def step():
                with mx.autograd.record():
                    out = invoke(schema, arrays, dict(attrs))
                    head = (out[0] if isinstance(out, list) else out).sum()
                head.backward()
                head.wait_to_read()

            try:
                for _ in range(warmup):
                    step()
                t0 = time.perf_counter()
                for _ in range(runs):
                    step()
                bwd_ms = (time.perf_counter() - t0) / runs * 1e3
            except Exception:
                bwd_ms = None
        return {"op": name, "avg_forward_ms": round(fwd_ms, 4),
                "avg_fwd_bwd_ms": round(bwd_ms, 4) if bwd_ms else None}
    except Exception as e:  # keep the sweep going
        return {"op": name, "error": str(e)[:200]}


def run_benchmark(ops=None, warmup=3, runs=20):
    results = [bench_op(op, warmup, runs) for op in ops or _DEFAULT_SET]
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names")
    ap.add_argument("--all", action="store_true",
                    help="sweep every registered op with generic inputs")
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    if args.ops:
        ops = args.ops.split(",")
    elif args.all:
        from mxnet_tpu.ops.registry import list_ops

        ops = list_ops()
    else:
        ops = _DEFAULT_SET
    results = run_benchmark(ops, runs=args.runs)
    text = json.dumps(results, indent=1)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
