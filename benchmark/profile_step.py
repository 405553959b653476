"""Capture an XLA op-level time breakdown of the ResNet train step.

Usage:
    python benchmark/profile_step.py [--model resnet50_v1] [--batch 128]
        [--layout NHWC] [--s2d 1] [--bf16 1] [--steps 5] [--top 30]
        [--step-mode {sharded,eager,compiled}]

``--step-mode eager`` profiles the Gluon eager-tape train step
(record/backward/trainer.step); ``--step-mode compiled`` profiles the
same model through ``Trainer.compile_step`` (cached_step.TrainStep, one
donated program) — the A/B for the whole-step fusion claim.  Each run
appends its header + by-kind table to
``benchmark/artifacts/profile_step_<mode>.log``.

Writes a jax.profiler trace to --logdir (default /tmp/jaxprof) and then
parses the Chrome-trace export (plugins/profile/*/…trace.json.gz) to print
the top ops by total self time on the device track, grouped by a coarse
kind (conv / fusion / reduce / copy-layout / matmul / other).  This is the
measurement tool behind docs/PERF.md's MFU analysis; it exists so kernel
work is guided by the actual step texture rather than FLOP models.

Reference analog: the profiler flow of docs/static_site/.../profiler.md
(reference python/mxnet/profiler.py) — here the source of truth is the
XLA device trace rather than engine-push brackets.
"""
import argparse
import collections
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402


def build_step(model_name, batch, layout, s2d, bf16, img=224):
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo import vision

    kw = {}
    if model_name.startswith("resnet"):
        kw = {"layout": layout, "input_layout": layout, "stem_s2d": s2d}
    net = vision.get_model(model_name, classes=1000, **kw)
    net.initialize(mx.init.Xavier())
    probe = (1, img, img, 3) if layout == "NHWC" else (1, 3, img, img)
    cpus = jax.devices("cpu") if jax.default_backend() != "cpu" else None
    if cpus:
        with jax.default_device(cpus[0]):
            net(mx.nd.zeros(probe))
    else:
        net(mx.nd.zeros(probe))
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 1})
    tr = par.ShardedTrainer(
        net, lambda o, l: ce(o, l).mean(), mesh, optimizer="sgd",
        optimizer_params={"lr": 0.1, "momentum": 0.9, "wd": 1e-4},
        compute_dtype=jnp.bfloat16 if bf16 else None)
    rng = onp.random.RandomState(0)
    shape = (batch, img, img, 3) if layout == "NHWC" else (batch, 3, img, img)
    data = rng.rand(*shape).astype(onp.float32)
    label = rng.randint(0, 1000, (batch,)).astype(onp.int32)
    data, label = tr.stage(data, label)
    return tr, data, label


def build_gluon_step(model_name, batch, layout, s2d, bf16, step_mode,
                     img=224):
    """Eager-tape vs compiled-TrainStep A/B builder (--step-mode): the
    same Gluon model/optimizer driven either through record()/backward()/
    trainer.step() (one XLA program per tape node + group programs) or
    through trainer.compile_step() (ONE donated program).  This is the
    measurement lane for the PR-3 fusion claim: the by-kind table should
    show the reduce+copy share dropping in compiled mode, where XLA sees
    BN batch-stats forward and the dy reductions backward together."""
    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo import vision

    kw = {}
    if model_name.startswith("resnet"):
        kw = {"layout": layout, "input_layout": layout, "stem_s2d": s2d}
    net = vision.get_model(model_name, classes=1000, **kw)
    net.initialize(mx.init.Xavier())
    if bf16:
        amp.init("bfloat16")
    probe = (1, img, img, 3) if layout == "NHWC" else (1, 3, img, img)
    net(mx.nd.zeros(probe))
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
    rng = onp.random.RandomState(0)
    shape = (batch, img, img, 3) if layout == "NHWC" \
        else (batch, 3, img, img)
    data = mx.nd.array(rng.rand(*shape).astype(onp.float32))
    label = mx.nd.array(
        rng.randint(0, 1000, (batch,)).astype(onp.int32))
    loss_fn = lambda n, d, l: ce(n(d), l).mean()
    if step_mode == "compiled":
        step = trainer.compile_step(net, loss_fn)

        def run_step():
            return step(data, label, batch_size=batch)
    else:
        def run_step():
            with mx.autograd.record():
                loss = loss_fn(net, data, label)
            loss.backward()
            trainer.step(batch)
            return loss

    return run_step


def build_decode_step(batch, seq):
    """``--step-mode decode``: profile the continuous-batching decode
    program (serving_decode.GenerativeEngine) — ``run_step()`` is one
    concurrent token-generation burst (``batch`` requests × 4 tokens),
    so the trace shows the ONE fused decode program's page gather /
    attention / scatter texture rather than per-request host noise."""
    import threading

    import numpy as onp

    from mxnet_tpu import serving_decode as sd

    model = sd.TinyCausalLM(vocab=512, d_model=256, n_layers=4,
                            n_heads=8, max_seq=max(seq, 64))
    pool = sd.PagePool(pages=max(64, batch * (seq // 16 + 2)), page=16)
    eng = sd.GenerativeEngine(model, pool=pool, max_rows=batch,
                              name="profile")
    eng.warmup(max_len=seq)
    rng = onp.random.RandomState(0)
    prompts = [rng.randint(0, 512, size=seq // 2).tolist()
               for _ in range(batch)]

    def run_step():
        errs = []

        def fire(p):
            try:
                eng.generate(p, max_new_tokens=4)
            except BaseException as e:
                errs.append(e)
        threads = [threading.Thread(target=fire, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        return float(batch * 4)          # tokens generated

    return run_step


def classify(name):
    n = name.lower()
    if "conv" in n:
        return "conv"
    if n.startswith("fusion") or ".fusion" in n:
        return "fusion"
    if "reduce" in n:
        return "reduce"
    if "copy" in n or "transpose" in n or "bitcast" in n:
        return "copy/layout"
    if "dot" in n or "matmul" in n:
        return "matmul"
    if "dynamic" in n or "scatter" in n or "gather" in n:
        return "gather/scatter"
    return "other"


def parse_trace(logdir, top, save_path=None):
    """Print the by-kind/by-op device-time tables; with ``save_path``
    also append them to an artifact log (the --step-mode A/B evidence)."""
    lines = []

    def emit(*parts):
        line = " ".join(str(p) for p in parts)
        lines.append(line)
        print(line)

    def flush():
        if save_path:
            os.makedirs(os.path.dirname(save_path), exist_ok=True)
            with open(save_path, "a") as f:
                f.write("\n".join(lines) + "\n")
            print(f"(appended to {save_path})")

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        emit("no trace.json.gz found under", logdir)
        flush()
        return
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # device-track pids: their thread names look like "XLA Ops" / TensorFlow
    # op tracks; host python tracks are excluded by requiring the 'dur' field
    # and picking pids whose process name mentions TPU / device.
    pid_names = {}
    tid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev["args"].get("name", "")
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tid_names[(ev["pid"], ev.get("tid"))] = ev["args"].get("name", "")
    device_pids = {p for p, n in pid_names.items()
                   if any(k in n for k in ("TPU", "Device", "/device:"))}
    if not device_pids:
        emit("WARNING: no device track found in the trace — counting ALL "
             "tracks (host rows included); op totals are not device time")
    per_op = collections.Counter()
    per_kind = collections.Counter()
    # per-fusion cost accounting (the ROADMAP-2 MFU substrate): XLA op
    # events carry per-execution "flops" / "bytes accessed" args on
    # device traces — summed per op name they give each fusion's
    # achieved FLOP/s and HBM bandwidth, which is what decides whether
    # a fusion is compute- or memory-bound and worth a Pallas kernel
    per_flops = collections.Counter()
    per_bytes = collections.Counter()
    total = 0.0
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        if device_pids and ev.get("pid") not in device_pids:
            continue
        tname = tid_names.get((ev.get("pid"), ev.get("tid")), "")
        # XLA op-level rows live on "XLA Ops"-style threads; step/module
        # rows would double count
        if tname and ("step" in tname.lower() or "module" in tname.lower()):
            continue
        dur = ev["dur"]  # us
        per_op[ev["name"]] += dur
        per_kind[classify(ev["name"])] += dur
        total += dur
        for k, v in (ev.get("args") or {}).items():
            lk = k.lower()
            try:
                val = float(str(v).replace(",", ""))
            except (TypeError, ValueError):
                continue
            if "flop" in lk and "util" not in lk:
                per_flops[ev["name"]] += val
            elif "bytes" in lk and ("accessed" in lk or lk == "bytes"):
                per_bytes[ev["name"]] += val
    emit(f"\n== device op time (total {total/1e3:.2f} ms across "
         f"{len(per_op)} op names; trace {os.path.basename(paths[-1])}) ==")
    emit("\n-- by kind --")
    for kind, dur in per_kind.most_common():
        emit(f"  {kind:<16} {dur/1e3:10.2f} ms  "
             f"{100*dur/max(total,1e-9):5.1f}%")
    emit(f"\n-- top {top} ops --")
    for name, dur in per_op.most_common(top):
        emit(f"  {dur/1e3:9.2f} ms  {100*dur/max(total,1e-9):5.1f}%  "
             f"{name[:110]}")
    # top-N FUSION cost table: time + bytes-accessed + flops columns,
    # with derived GFLOP/s / GB/s so the top offender's roofline
    # position reads straight off the log
    fusions = [(n, d) for n, d in per_op.most_common()
               if classify(n) == "fusion"][:top]
    if fusions:
        emit(f"\n-- top {len(fusions)} fusions by device time "
             "(bytes/flops from trace args; '-' = not reported) --")
        emit(f"  {'ms':>9} {'%':>5} {'GFLOP':>9} {'GB':>8} "
             f"{'GFLOP/s':>9} {'GB/s':>8}  name")
        for name, dur in fusions:
            fl, by = per_flops.get(name), per_bytes.get(name)
            sec = dur / 1e6
            emit("  "
                 f"{dur/1e3:9.2f} {100*dur/max(total,1e-9):5.1f} "
                 + (f"{fl/1e9:9.2f} " if fl else f"{'-':>9} ")
                 + (f"{by/1e9:8.3f} " if by else f"{'-':>8} ")
                 + (f"{fl/sec/1e9:9.1f} " if fl and sec else f"{'-':>9} ")
                 + (f"{by/sec/1e9:8.1f}  " if by and sec
                    else f"{'-':>8}  ")
                 + name[:80])
    else:
        emit("\n-- no fusion ops in this trace (CPU traces name kernels "
             "differently; run on device for the fusion table) --")
    flush()


def main():
    from mxnet_tpu import program_store

    # the persistent XLA cache at the repo's one resolved path — a profile
    # run of the bench's own step must hit the bench's cache, not
    # recompile cold
    program_store.enable_persistent_cache(min_compile_secs=5)
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--layout", default="NHWC")
    ap.add_argument("--s2d", type=int, default=1)
    ap.add_argument("--bf16", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--logdir", default="/tmp/jaxprof")
    ap.add_argument("--step-mode", default="sharded",
                    choices=("sharded", "eager", "compiled", "decode"),
                    help="sharded = the ShardedTrainer compiled step "
                         "(historical default); eager vs compiled A/B the "
                         "Gluon tape against cached_step.TrainStep — the "
                         "reduce+copy share should drop in compiled mode; "
                         "decode profiles the serving_decode continuous-"
                         "batching token-decode program (--batch rows, "
                         "BENCH_SEQ-ish --seq context)")
    ap.add_argument("--seq", type=int, default=128,
                    help="decode mode: max context length (prompt seq/2)")
    ap.add_argument("--parse-only", action="store_true",
                    help="just parse an existing --logdir trace")
    args = ap.parse_args()

    artifact = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "artifacts",
        f"profile_step_{args.step_mode}.log")
    if not args.parse_only:
        import jax
        if args.step_mode == "sharded":
            tr, data, label = build_step(args.model, args.batch,
                                         args.layout, bool(args.s2d),
                                         bool(args.bf16))
            run_step = lambda: tr.step(data, label, sync=False)
            print("compiling…")
            t0 = time.perf_counter()
            tr.step(data, label)
            print(f"compiled in {time.perf_counter()-t0:.1f}s; warming")
        elif args.step_mode == "decode":
            # decode rows default smaller than a train batch; the
            # img/s figures below then read as requests/s-ish (each
            # run_step = batch requests x 4 tokens)
            args.batch = args.batch if args.batch != 128 else 16
            run_step = build_decode_step(args.batch, args.seq)
            print(f"warming (decode step, {args.batch} rows)…")
        else:
            run_step = build_gluon_step(args.model, args.batch,
                                        args.layout, bool(args.s2d),
                                        bool(args.bf16), args.step_mode)
            print(f"warming ({args.step_mode} step)…")
        for _ in range(2):
            loss = run_step()
        loss = getattr(loss, "asnumpy", lambda: loss)()
        float(loss if getattr(loss, "ndim", 0) == 0 else loss.ravel()[0])
        os.makedirs(args.logdir, exist_ok=True)
        jax.profiler.start_trace(args.logdir)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss = run_step()
        loss = getattr(loss, "asnumpy", lambda: loss)()
        v = float(loss if getattr(loss, "ndim", 0) == 0
                  else loss.ravel()[0])
        dt = time.perf_counter() - t0
        jax.profiler.stop_trace()
        print(f"[{args.step_mode}] {args.steps} steps in {dt*1e3:.1f} ms "
              f"({args.batch*args.steps/dt:.1f} img/s, loss {v:.3f})")
        os.makedirs(os.path.dirname(artifact), exist_ok=True)
        with open(artifact, "a") as f:
            f.write(f"\n== {time.strftime('%Y-%m-%d %H:%M:%S')} "
                    f"{args.model} bs{args.batch} {args.layout} "
                    f"bf16={args.bf16} mode={args.step_mode}: "
                    f"{args.steps} steps {dt*1e3:.1f} ms "
                    f"({args.batch*args.steps/dt:.1f} img/s) ==\n")
    parse_trace(args.logdir, args.top, save_path=artifact)


if __name__ == "__main__":
    main()
