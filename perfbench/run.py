#!/usr/bin/env python3
"""One cell of the benchmark, one process, one last line of JSON.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, a device ``busy_s``/``window_s`` and a ``breakdown``.
Without a TPU, or with fewer chips than the cell asks for, it exits nonzero
and prints no result.  ``--rehearse`` runs the same code at the toy sizes of
the files' ``rehearse`` groups, allows the CPU and prefixes EVERY line,
the last one included, with ``REHEARSAL``: it is for the tests and for
finding faults before a chip call, never a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()        # set-up is counted from here

import argparse
import json
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import manifest       # noqa: E402  (needs ROOT on the path)


def main(argv=None, t_start=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    prefix = "REHEARSAL " if args.rehearse else ""

    def say(msg):
        print(f"{prefix}[perfbench] {msg}", file=sys.stderr, flush=True)

    try:
        cell = manifest.resolve(args.workload, args.rehearse)
    except manifest.ManifestError as e:
        say(f"refused: {e}")
        return 2
    seconds = cell.run_seconds if args.seconds is None else args.seconds

    # the cell's environment, before the program is imported
    saved = {k: os.environ.get(k) for k in cell.mix["env"]}
    os.environ.update(cell.mix["env"])
    try:
        import jax

        devices = jax.devices()
        kind = devices[0].device_kind
        if devices[0].platform != "tpu" and not args.rehearse:
            say(f"refused: needs a TPU, but jax found "
                f"{devices[0].platform!r} ({kind}, {len(devices)} device(s)). "
                "Nothing was run; nothing falls back to the CPU.")
            return 2
        if len(devices) < cell.chips:
            say(f"refused: cell {cell.name} needs {cell.chips} chip(s), "
                f"jax found {len(devices)}")
            return 2
        try:
            peak = manifest.peak_for(kind)
        except manifest.ManifestError as e:
            if not args.rehearse:
                say(f"refused: {e}")
                return 2
            peak = manifest.peak_for("TPU v5 lite")
            say(f"{kind!r} has no peaks: the v5e's stand in (rehearsal only)")

        import mxnet_tpu

        if os.path.dirname(os.path.dirname(
                os.path.abspath(mxnet_tpu.__file__))) != ROOT:
            say(f"refused: mxnet_tpu was imported from {mxnet_tpu.__file__}, "
                f"not from this checkout ({ROOT})")
            return 2
        say(f"cell {cell.name}: config {cell.config}, mix {cell.traffic} "
            f"{cell.mix['env']}, {cell.chips} x {kind}, seed {args.seed}, "
            f"{seconds} s, trace {args.trace}")
        opts = SimpleNamespace(
            seed=args.seed, seconds=seconds, trace=bool(args.trace),
            rehearse=args.rehearse,
            t_start=T_START if t_start is None else t_start,
            out_dir=os.path.join(ROOT, ".perfbench_out", cell.name))
        os.makedirs(opts.out_dir, exist_ok=True)
        result = cell.driver.run(cell, opts, devices[:cell.chips], peak, say)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    trace = result["obs"]["trace"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device,
            "workload": cell.name, "seed": args.seed,
            "checks": result["checks"],
            "setup_breakdown_s": result["setup"]}
    if not args.trace:
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {
                "value": result["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        if trace is None and not args.rehearse:
            say("refused: the traced window holds no device operation")
            return 3
        for m in cell.per_layer:
            value = manifest.load_module("layer_metrics", m["name"]).read(
                result["obs"])
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"],
                                 "category_s": trace["category_s"]}
    print(prefix + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
