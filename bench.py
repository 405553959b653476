"""Headline benchmarks: one invocation, ALL lanes, one JSON line.

Lanes (each with achieved_tflops + mfu): ResNet-50 fp32 train, ResNet-50
bf16 mixed-precision train, BERT-base bf16 train, ResNet-50 int8
inference (compile time logged); counter-based lanes ride along without
an MFU figure: train_step (compiled-step dispatch budget), infer
(bucketed serving p99), decode (continuous-batching generative serving:
tokens/s A/B + multi-tenant storm), pipeline (device idle gap), and
multichip (1->N weak scaling).  Methodology matches the reference's
benchmark_score.py (synthetic data, steady-state throughput; docs
perf.md — V100 fp32 train 298.51 img/s at bs32 is BASELINE.md's anchor;
perf.md:208's fp16 V100 2,085 img/s inference is the mixed-precision
sanity anchor).

The whole train step (fwd, bwd, update) is one donated XLA program via
ShardedTrainer on a 1-chip mesh; the bf16 lane keeps fp32 master weights
and casts compute to bf16 (the MXU-native path).

FLOP model (documented so the TFLOP numbers are auditable):
- ResNet-50 @224: 4.1 GFLOP/img forward (standard literature count,
  multiply+add = 2 FLOPs); training = 3x forward (bwd ~ 2x fwd).
- BERT-base: 6*N FLOPs/token train (N = param count) + 12*L*s*d
  attention term.
- int8 inference: 8.2 GOP/img (4.1 G MACs x 2).
MFU divides by the chip's matmul-unit peak (bf16 peak for fp32 too:
TPU fp32 matmuls decompose onto the same bf16 MXU passes) — the
``mfu_basis`` field names the peak used.

Process structure: EVERY LANE RUNS IN ITS OWN SUBPROCESS, one at a time,
because a chip belongs to one process.  The parent never imports jax; it
probes the backend ONCE in a bounded subprocess and, when the probe finds
no accelerator, every lane is an error lane — nothing is re-run on the CPU.
A lane child (``--lane NAME``) that itself starts a worker subprocess
(train_step, infer, decode, pipeline, multichip, moe, pp, elastic) stays
off jax too, so the worker is the only process that opens the chip.  The
parent kills a lane that exceeds its budget.  Every lane stamps
``compile_s`` plus the program-store persistent-cache
``cache_hits``/``cache_misses``, and the final payload carries
``cold_start_s`` (process start -> first result).  A separate watchdog
process remains as a backstop that emits completed lanes if the parent
itself dies; a done-marker file prevents the double-emit race.  Progress
on stderr, stdout is ONE parseable JSON line.  Timing discipline inside
lanes: warm with steps + a HOST VALUE READ, fence the timed region with
another host read (an enqueue returns before the device finishes).

Env: BENCH_MODEL=all|resnet50_v1|resnet50_v1_bf16|bert|train_step|infer|
pipeline|resnet50_v1_int8, BENCH_BATCH, BENCH_IMG, BENCH_STEPS,
BENCH_TIMEOUT, BENCH_PROBE_TIMEOUT, BENCH_LANE_TIMEOUT.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

V100_RESNET50_TRAIN_IMGS_PER_SEC = 298.51  # reference perf.md:252, bs32 fp32
V100_BERT_BASE_TOKENS_PER_SEC = 11500.0    # fp16 V100 BERT-base pretrain
V100_RESNET50_FP32_INFER_IMGS_PER_SEC = 1076.81  # perf.md:194

RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.1e9
RESNET50_INFER_OPS_PER_IMG = 2 * 4.1e9

# matmul-unit peak per chip generation (dense, per chip)
PEAK_TFLOPS = {
    "TPU v5 lite": {"bf16": 197.0, "int8": 394.0},
    "TPU v5e": {"bf16": 197.0, "int8": 394.0},
    "TPU v4": {"bf16": 275.0, "int8": 275.0},
    "TPU v5": {"bf16": 459.0, "int8": 918.0},
    "TPU v5p": {"bf16": 459.0, "int8": 918.0},
    "TPU v6 lite": {"bf16": 918.0, "int8": 1836.0},
}

_T0 = time.time()
_RESULT_EMITTED = threading.Event()
_EMIT_LOCK = threading.Lock()
_LANES: list = []          # completed lane dicts (watchdog emits these)
_FIRST_RESULT_T: list = []  # wall time of the first lane with a result —
                            # emitted as cold_start_s (process start →
                            # first result), the cold-start-tax headline
_PARTIAL_PATH = os.environ.get(
    "BENCH_PARTIAL_PATH", f"/tmp/bench_partial_{os.getpid()}.ndjson")


def _progress(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _git_head() -> str:
    """Commit the benchmark was captured at (provenance stamp, ADVICE r5);
    'unknown' outside a git checkout."""
    try:
        r = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        if r.returncode == 0:
            return r.stdout.strip()
    except Exception:
        pass
    return "unknown"


def _device_kind() -> str:
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def _peak(kind: str) -> float:
    dk = _device_kind()
    for prefix, peaks in PEAK_TFLOPS.items():
        if dk.startswith(prefix):
            return peaks.get(kind, 0.0)
    return 0.0


def _with_mfu(lane: dict, flops_per_unit: float, kind: str) -> dict:
    """Attach achieved_tflops / mfu to a lane from its value (units/s)."""
    tflops = lane["value"] * flops_per_unit / 1e12
    lane["achieved_tflops"] = round(tflops, 2)
    peak = _peak(kind)
    if peak > 0:
        lane["mfu"] = round(tflops / peak, 4)
        lane["mfu_basis"] = f"{kind} peak {peak:g} TFLOP/s ({_device_kind()})"
    else:
        lane["mfu"] = None
        lane["mfu_basis"] = f"unknown peak for {_device_kind()}"
    return lane


def _headline(lanes: list) -> dict:
    """The driver's single metric line: best ResNet-50 train lane."""
    order = ("resnet50_v1_bf16_train_throughput_per_chip",
             "resnet50_v1_train_throughput_per_chip")
    for metric in order:
        for lane in lanes:
            if lane.get("metric") == metric and lane.get("value", 0) > 0:
                return dict(lane)
    if lanes:
        return dict(lanes[0])
    return {"metric": "resnet50_v1_train_throughput_per_chip",
            "value": 0.0, "unit": "img/s", "vs_baseline": 0.0,
            "error": "no lane completed"}


def _emit_final(error: str = "") -> None:
    with _EMIT_LOCK:
        if _RESULT_EMITTED.is_set():
            return
        _RESULT_EMITTED.set()
        payload = _headline(_LANES)
        if error:
            payload["error"] = error[:400]
        payload["lanes"] = _LANES
        # process start -> first completed lane result: the number the
        # persistent program cache exists to shrink (ROADMAP item 4)
        payload["cold_start_s"] = (round(_FIRST_RESULT_T[0] - _T0, 1)
                                   if _FIRST_RESULT_T else None)
        # provenance: stamp the commit this run measured, so later readers
        # can tell whether any referenced artifact is the same code
        head = _git_head()
        payload["git_commit"] = head
        print(json.dumps(payload), flush=True)
        try:   # stand the watchdog down: we own the stdout line now
            open(_PARTIAL_PATH + ".done", "w").close()
        except OSError:
            pass


_WATCHDOG_CODE = r"""
import json, os, signal, sys, time
parent, deadline, partial = int(sys.argv[1]), float(sys.argv[2]), sys.argv[3]
while time.time() < deadline:
    try:
        os.kill(parent, 0)
    except OSError:
        sys.exit(0)                      # parent finished normally
    if os.path.exists(partial + ".done"):
        sys.exit(0)                      # parent already emitted its line
    time.sleep(1.0)
# deadline passed with the parent still running.  Give it a short grace:
# if it emits (done-marker appears) or exits, stand down — otherwise two
# JSON lines would race on the shared stdout.
for _ in range(10):
    if os.path.exists(partial + ".done"):
        sys.exit(0)
    try:
        os.kill(parent, 0)
    except OSError:
        sys.exit(0)
    time.sleep(0.5)
# emit whatever lanes the parent persisted, on the SHARED stdout, then
# kill it
lanes = []
try:
    with open(partial) as f:
        lanes = [json.loads(l) for l in f if l.strip()]
except OSError:
    pass
head = dict(lanes[0]) if lanes else {
    "metric": "resnet50_v1_train_throughput_per_chip", "value": 0.0,
    "unit": "img/s", "vs_baseline": 0.0}
for lane in lanes:
    if lane.get("metric", "").startswith("resnet50_v1_bf16") and \
            lane.get("value", 0) > 0:
        head = dict(lane)
        break
head["error"] = "watchdog timeout (device backend stalled)"
head["lanes"] = lanes
print(json.dumps(head), flush=True)
try:
    os.kill(parent, signal.SIGKILL)
except OSError:
    pass
sys.exit(3)
"""


def _watchdog(timeout_s: float) -> None:
    """A SEPARATE PROCESS sharing our stdout: an in-process daemon thread
    starves when the main thread blocks inside a C call holding the GIL.
    The child only needs the partial-lane file and our pid."""
    try:
        open(_PARTIAL_PATH, "w").close()
        subprocess.Popen(
            [sys.executable, "-c", _WATCHDOG_CODE, str(os.getpid()),
             str(_T0 + timeout_s), _PARTIAL_PATH],
            stdout=sys.stdout, stderr=subprocess.DEVNULL)
    except Exception as e:                       # bench still runs unguarded
        _progress(f"watchdog spawn failed: {e}")


def _probe_device_backend(timeout_s: float) -> "tuple[bool, str]":
    """ONE bounded probe: a tiny matmul in a SUBPROCESS (the parent stays
    off jax; the probe process exits, and releases the chip, before any
    lane starts).  Returns (probe_ok, platform)."""
    code = ("import jax, jax.numpy as jnp; "
            "x = jnp.ones((256, 256)); "
            "v = float((x @ x)[0, 0]); "
            "print(jax.devices()[0].platform, v)")
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _progress(f"device probe TIMED OUT after {timeout_s:.0f}s")
        return False, ""
    if r.returncode != 0:
        _progress("device probe failed: " + r.stderr.strip()[-400:])
        return False, ""
    _progress("device probe OK: " + r.stdout.strip())
    return True, r.stdout.strip().split()[0]


# ---------------------------------------------------------------------------
# lanes
# ---------------------------------------------------------------------------

def lane_train(on_cpu: bool, bf16: bool,
               model_name: str = "resnet50_v1") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import config
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo import vision

    tag = f"{model_name} {'bf16' if bf16 else 'fp32'}"
    # bf16 default 128: the measured v5e sweet spot (batch sweep 64..512
    # peaked there — larger batches are slightly activation-bound); fp32
    # keeps 256 for continuity with the round-2 artifact
    batch = config.get("BENCH_BATCH",
                       default=8 if on_cpu else (128 if bf16 else 256))
    steps = config.get("BENCH_STEPS", default=3 if on_cpu else 40)
    img = config.get("BENCH_IMG")
    # ResNet runs channel-minor with the space-to-depth stem by default:
    # both are exact rewrites of the reference model (asserted by
    # tests/test_resnet_layout.py), chosen because NHWC keeps convs and BN
    # reductions on XLA's native TPU tiling and the s2d stem widens conv0's
    # contraction onto the MXU (MLPerf ResNet trick).  BENCH_LAYOUT=NCHW /
    # BENCH_S2D=0 restore the reference texture.
    is_resnet = model_name.startswith("resnet")
    layout = config.get("BENCH_LAYOUT") if is_resnet else "NCHW"
    s2d = bool(config.get("BENCH_S2D")) and is_resnet
    model_kw = {}
    if is_resnet:
        model_kw = {"layout": layout, "input_layout": layout,
                    "stem_s2d": s2d}
    _progress(f"{tag}: building (batch={batch} img={img} layout={layout} "
              f"s2d={s2d})")
    net = vision.get_model(model_name, classes=1000, **model_kw)
    net.initialize(mx.init.Xavier())
    probe_shape = ((1, img, img, 3) if layout == "NHWC"
                   else (1, 3, img, img))
    # deferred-shape probe on HOST CPU: keeps its stream of tiny per-op
    # compiles off the accelerator
    cpu0 = jax.devices("cpu")[0] if not on_cpu else None
    if cpu0 is not None:
        with jax.default_device(cpu0):
            net(mx.nd.zeros(probe_shape))
    else:
        net(mx.nd.zeros(probe_shape))
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 1})
    tr = par.ShardedTrainer(
        net, lambda o, l: ce(o, l).mean(), mesh, optimizer="sgd",
        optimizer_params={"lr": 0.1, "momentum": 0.9, "wd": 1e-4},
        compute_dtype=jnp.bfloat16 if bf16 else None)
    rng = onp.random.RandomState(0)
    data_shape = ((batch, img, img, 3) if layout == "NHWC"
                  else (batch, 3, img, img))
    data = rng.rand(*data_shape).astype(onp.float32)
    label = rng.randint(0, 1000, (batch,)).astype(onp.int32)
    data, label = tr.stage(data, label)
    _progress(f"{tag}: compiling whole-graph train step")
    t_c = time.perf_counter()
    tr.step(data, label)          # compile + sync
    compile_s = time.perf_counter() - t_c
    _progress(f"{tag}: compiled in {compile_s:.1f}s; warming")
    for _ in range(2):
        loss = tr.step(data, label, sync=False)
    float(loss.asnumpy() if hasattr(loss, "asnumpy") else loss)
    _progress(f"{tag}: timing {steps} steps")
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = tr.step(data, label, sync=False)
    loss_val = float(loss.asnumpy() if hasattr(loss, "asnumpy") else loss)
    dt = time.perf_counter() - t0
    imgs_per_sec = batch * steps / dt
    _progress(f"{tag}: {imgs_per_sec:.2f} img/s "
              f"(final loss {loss_val:.3f})")
    suffix = "_bf16" if bf16 else ""
    # the FLOP model and the V100 anchor are ResNet-50 numbers: any other
    # zoo model reports 0.0/None rather than a wrong ratio (same policy
    # as lane_int8)
    is_r50 = model_name == "resnet50_v1"
    lane = {
        "metric": f"{model_name}{suffix}_train_throughput_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(imgs_per_sec
                             / V100_RESNET50_TRAIN_IMGS_PER_SEC, 3)
        if is_r50 else 0.0,
        "batch": batch,
        "layout": layout,
        "stem_s2d": s2d,
        # stamped so A/B rounds read it off the artifact: the MXU
        # channel-alignment pass (MXNET_PAD_CHANNELS)
        "pad_channels": int(config.get("MXNET_PAD_CHANNELS")),
        "compile_s": round(compile_s, 1),
        "platform": jax.default_backend(),
    }
    if not is_r50:
        lane["achieved_tflops"] = None
        lane["mfu"] = None
        lane["mfu_basis"] = f"no FLOP model for {model_name}"
        return lane
    return _with_mfu(lane, RESNET50_TRAIN_FLOPS_PER_IMG, "bf16")


def lane_bert(on_cpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu import config, models
    from mxnet_tpu import parallel as par

    batch = config.get("BENCH_BATCH", default=4 if on_cpu else 32)
    seq = config.get("BENCH_SEQ")
    steps = config.get("BENCH_STEPS", default=2 if on_cpu else 20)
    accum = config.get("BENCH_ACCUM")
    _progress(f"bert: init params (batch={batch} seq={seq})")
    cfg = models.TransformerLMConfig(dtype=jnp.bfloat16)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(onp.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    flops_per_token = (6 * n_params
                       + 12 * cfg.num_layers * seq * cfg.hidden)
    mesh = par.make_mesh({"dp": 1})
    with mesh:
        m, v = models.init_opt_state(params)
        step = models.make_train_step(cfg, mesh, optimizer="adam", lr=1e-4,
                                      grad_accum=accum)
        rng = onp.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                           jnp.int32)
        _progress("bert: compiling train step")
        t_c = time.perf_counter()
        params, m, v, loss = step(params, m, v, toks, toks, jnp.float32(1))
        jax.block_until_ready(loss)
        compile_s = time.perf_counter() - t_c
        for _ in range(3):
            params, m, v, loss = step(params, m, v, toks, toks,
                                      jnp.float32(1))
        float(loss)                          # host read = queue drain
        _progress(f"bert: warmed, timing {steps} steps")
        t0 = time.perf_counter()
        for _ in range(steps):
            params, m, v, loss = step(params, m, v, toks, toks,
                                      jnp.float32(1))
        loss_val = float(loss)               # hard fence, in-region
        dt = time.perf_counter() - t0
        _progress(f"bert: final loss {loss_val:.4f}")
    tokens_per_sec = batch * seq * steps / dt
    _progress(f"bert: {tokens_per_sec:.0f} tokens/s "
              f"({n_params / 1e6:.0f}M params)")
    lane = {
        "metric": "bert_base_train_throughput_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / V100_BERT_BASE_TOKENS_PER_SEC,
                             3),
        "batch": batch,
        "seq": seq,
        "compile_s": round(compile_s, 1),
        "platform": jax.default_backend(),
    }
    return _with_mfu(lane, float(flops_per_token), "bf16")


def lane_int8(on_cpu: bool, model_name: str = "resnet50_v1") -> dict:
    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import config
    from mxnet_tpu.contrib import quantization as quant
    from mxnet_tpu.gluon.model_zoo import vision

    batch = config.get("BENCH_BATCH", default=8 if on_cpu else 64)
    steps = config.get("BENCH_STEPS", default=3 if on_cpu else 20)
    img = config.get("BENCH_IMG", default=64 if on_cpu else 224)
    # same channel-minor fast path as the train lanes (quantized_conv and
    # the BN fold are layout-general); BENCH_LAYOUT=NCHW restores the
    # reference texture
    is_resnet = model_name.startswith("resnet")
    layout = config.get("BENCH_LAYOUT") if is_resnet else "NCHW"
    s2d = bool(config.get("BENCH_S2D")) and is_resnet
    model_kw = ({"layout": layout, "input_layout": layout, "stem_s2d": s2d}
                if is_resnet else {})
    _progress(f"int8: building {model_name} (batch={batch} img={img} "
              f"layout={layout} s2d={s2d})")
    net = vision.get_model(model_name, classes=1000, **model_kw)
    net.initialize(mx.init.Xavier())
    cpu0 = jax.devices("cpu")[0] if jax.default_backend() != "cpu" else None
    rng = onp.random.RandomState(0)
    dshape = ((batch, img, img, 3) if layout == "NHWC"
              else (batch, 3, img, img))
    probe = mx.nd.array(rng.rand(*dshape).astype(onp.float32))
    calib = [mx.nd.array(rng.rand(*dshape).astype(onp.float32))
             for _ in range(2)]
    # calibration stays on host CPU: an eager small-op stream
    _progress("int8: calibrating + converting (host CPU)")
    if cpu0 is not None:
        with jax.default_device(cpu0):
            net(probe)
            qnet = quant.quantize_net(net, calib)
        # conversion ran with a host-CPU default device: commit params to
        # the accelerator ONCE or every call re-transfers them
        qnet.stage()
        # the input must be COMMITTED to the accelerator too: nd.array's
        # default ctx is cpu (reference semantics), and a cpu-committed
        # input makes the whole jitted graph fail device placement against
        # the staged tpu params
        x = mx.nd.array(calib[0], ctx=mx.tpu(0))
    else:
        net(probe)
        qnet = quant.quantize_net(net, calib)
        x = calib[0]
    _progress("int8: compiling (fused conv+bn+relu graph, fused "
              "requantize epilogues)")
    t_c = time.perf_counter()
    out = qnet(x)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t_c
    _progress(f"int8: compiled in {compile_s:.1f}s")
    for _ in range(2):
        out = qnet(x)
    float(jax.device_get(out).ravel()[0])
    _progress(f"int8: timing {steps} steps")
    t0 = time.perf_counter()
    for _ in range(steps):
        out = qnet(x)
    float(jax.device_get(out).ravel()[0])
    dt = time.perf_counter() - t0
    imgs_per_sec = batch * steps / dt
    _progress(f"int8: {imgs_per_sec:.2f} img/s")
    # reference fp32 V100 inference baselines (perf.md:194); models
    # without a published number report 0.0 rather than a wrong ratio
    fp32_infer_baselines = {"resnet50_v1": 1076.81,
                            "resnet50_v2": 1076.81, "vgg16": 708.43}
    base = fp32_infer_baselines.get(model_name)
    lane = {
        "metric": f"{model_name}_int8_infer_throughput_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(imgs_per_sec / base, 3) if base else 0.0,
        "batch": batch,
        "pad_channels": int(config.get("MXNET_PAD_CHANNELS")),
        "compile_s": round(compile_s, 1),
        "platform": jax.default_backend(),
    }
    lane = _with_mfu(lane, RESNET50_INFER_OPS_PER_IMG, "int8")
    # Protect the headline before attempting the bf16 reference below: a
    # wall-budget overrun SIGKILLs this subprocess (no except path runs),
    # and the parent salvages the LAST parseable stdout line on timeout.
    print(json.dumps(lane), flush=True)
    def _unwrap(out):
        return out._data if hasattr(out, "_data") else out

    def _time_net(run):
        run()                                   # compile + fence
        for _ in range(2):
            run()
        float(jax.device_get(run()).ravel()[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            out = run()
        float(jax.device_get(out).ravel()[0])
        return batch * steps / (time.perf_counter() - t0)

    # bf16 inference at the SAME batch, same run: the claim that matters
    # is int8 beating bf16 inference ON THIS CHIP, so the ratio must be
    # a single-window artifact, not a cross-round comparison.
    try:
        from mxnet_tpu import amp
        _progress("int8: bf16 inference reference (matched batch)")
        bnet = amp.convert_hybrid_block(
            net, "bfloat16", ctx=None if on_cpu else mx.tpu(0))
        bnet.hybridize()

        bf16_ips = _time_net(lambda: _unwrap(bnet(x)))
        _progress(f"int8: bf16 inference ref {bf16_ips:.2f} img/s "
                  f"(int8 is {imgs_per_sec / bf16_ips:.2f}x)")
        lane["bf16_infer_ref"] = round(bf16_ips, 2)
        lane["vs_bf16_infer"] = round(imgs_per_sec / bf16_ips, 3)
    except Exception as exc:                    # pragma: no cover
        _progress(f"int8: bf16 inference reference skipped: {exc!r}")

    # quantized convs are always lax.conv s8 (the Pallas route measured
    # 0.345x of it, BENCH_builder_r05 pallas_vs_lax, and was deleted)
    lane["int8_path"] = "lax"
    return lane


def _fleet_telemetry_env(tag: str):
    """(env, dir) for a subprocess lane worker: the worker (and every
    process IT forks — drill children inherit the env) flushes an
    atomic per-process flight-recorder shard into ``dir`` on waitall/
    drain, so the lane can stamp FLEET telemetry, not just one
    process's (ISSUE 15)."""
    import tempfile

    d = tempfile.mkdtemp(prefix=f"bench-telemetry-{tag}-")
    env = dict(os.environ)
    env["MXNET_TELEMETRY_DIR"] = d
    return env, d


def _stamp_fleet_telemetry(lane: dict, tel_dir: str) -> dict:
    """Fold the worker fleet's shards (``telemetry.merge``) into the
    lane: summed cumulative counters under ``fleet_telemetry`` plus the
    process count — the check_perf_delta.py gate prefers this key."""
    try:
        from mxnet_tpu import telemetry as _tel

        merged = _tel.merge(tel_dir)
        if merged["shards"]:
            lane["fleet_telemetry"] = {
                k: v for k, v in merged["counters"].items() if v}
            lane["telemetry_processes"] = len(merged["shards"])
    except Exception:
        pass
    return lane


def lane_train_step(on_cpu: bool) -> dict:
    """Compiled whole-train-step lane (cached_step.TrainStep): runs
    benchmark/eager_latency.py's train_step_compiled worker and carries
    its counters into lanes[].  The value is dispatches/step — the PR-3
    acceptance bar is 1 (counter-based, so a direct ``--lane`` run under
    JAX_PLATFORMS=cpu checks it too); retrace/cache stats ride along for regression
    tracking.  A lane value of 0 means the compiled path fell back."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "eager_latency.py")
    r = subprocess.run([sys.executable, "-u", script, "--train-step-only",
                        "--json"], capture_output=True, text=True,
                       timeout=600, env=dict(os.environ))
    if r.returncode != 0:
        raise RuntimeError(
            f"train_step lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])["train_step_compiled"]
    _progress(f"train_step: {c['dispatches_per_step']:.1f} dispatches/step "
              f"({'compiled' if c['compiled'] else 'FELL BACK'}, "
              f"{c['us_per_step']:.0f} us/step)")
    return {
        "metric": "train_step_compiled_dispatches_per_step",
        "value": c["dispatches_per_step"] if c["compiled"] else 0.0,
        "unit": "dispatches/step",
        "vs_baseline": 0.0,
        "compiled": c["compiled"],
        "retrace_count": c["retrace_count"],
        "program_cache_hits": c["program_cache_hits"],
        "program_cache_misses": c["program_cache_misses"],
        "compile_s": c["compile_s"],
        "cache_hits": c["cache_hits"],
        "cache_misses": c["cache_misses"],
        "us_per_step": round(c["us_per_step"], 1),
        "n_params": c["n_params"],
        "telemetry": c.get("telemetry"),
        "platform": c["platform"],
    }


def lane_infer(on_cpu: bool) -> dict:
    """Shape-bucketed serving lane (serving.ServingEngine): runs
    benchmark/serving_latency.py's worker over a randomized
    variable-length request stream and carries its counters into
    lanes[].  The value is p99 request latency; the PR-4 acceptance bar
    rides along as counters — 0 retraces after warm-up with the program
    count bounded by the bucket grid (counter-based: a direct ``--lane`` run
    under JAX_PLATFORMS=cpu checks them too)."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "serving_latency.py")
    env, tel_dir = _fleet_telemetry_env("infer")
    r = subprocess.run([sys.executable, "-u", script, "--serve-only",
                        "--json"], capture_output=True, text=True,
                       timeout=600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"infer lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])["serving"]
    _progress(f"infer: p50 {c['p50_us']:.0f} us / p99 {c['p99_us']:.0f} us, "
              f"{c['throughput_rps']:.1f} req/s, "
              f"{c['retraces_after_warm']} retraces, "
              f"{c['programs']} programs")
    lane = {
        "metric": "serving_infer_p99_latency_us",
        "value": round(c["p99_us"], 1),
        "unit": "us",
        "vs_baseline": 0.0,
        "p50_us": round(c["p50_us"], 1),
        "throughput_rps": round(c["throughput_rps"], 1),
        "bucket_hits": c["bucket_hits"],
        "bucket_misses": c["bucket_misses"],
        "retrace_count": c["retraces_after_warm"],
        "programs": c["programs"],
        "warmup_programs": c["warmup_programs"],
        "compile_s": c["compile_s"],
        "cache_hits": c["cache_hits"],
        "cache_misses": c["cache_misses"],
        "buckets": c["buckets"],
        "requests_per_dispatch":
            round(c["concurrent"]["requests_per_dispatch"], 2),
        "telemetry": c.get("telemetry"),
        "platform": c["platform"],
    }
    return _stamp_fleet_telemetry(lane, tel_dir)


def lane_decode(on_cpu: bool) -> dict:
    """Continuous-batching generative-serving lane (PR 8,
    serving_decode.GenerativeEngine): runs benchmark/serving_latency.py's
    decode worker — the one-request-at-a-time vs continuous-batching A/B
    plus the multi-tenant storm — and carries its counters into
    lanes[].  The value is continuous-batching tokens/s; the acceptance
    bars ride along: batching_speedup >= 2 at concurrency >= 8, 0
    retraces after warm-up with programs == prefill buckets + 1, storm
    interference_p99_ratio <= 2 (fast model vs its solo p99) with a
    nonzero shed count under the deliberate overload (counter-based: a
    direct ``--lane`` run under JAX_PLATFORMS=cpu checks them too)."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "serving_latency.py")
    env, tel_dir = _fleet_telemetry_env("decode")
    r = subprocess.run([sys.executable, "-u", script, "--decode-only",
                        "--json"], capture_output=True, text=True,
                       timeout=600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"decode lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])["decode"]
    s = c.get("storm", {})
    _progress(f"decode: {c['continuous_tokens_s']:.0f} tok/s continuous "
              f"({c['batching_speedup']}x vs one-at-a-time), "
              f"{c['retraces_after_warm']} retraces, storm p99 ratio "
              f"{s.get('interference_p99_ratio', '-')}, "
              f"{s.get('shed_total', 0)} shed")
    lane = {
        "metric": "decode_continuous_tokens_per_s",
        "value": c["continuous_tokens_s"],
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "sequential_tokens_s": c["sequential_tokens_s"],
        "batching_speedup": c["batching_speedup"],
        "concurrency": c["concurrency"],
        "rows_per_decode": c["rows_per_decode"],
        "retrace_count": c["retraces_after_warm"],
        "programs": c["programs"],
        "warmup_programs": c["warmup_programs"],
        "p50_us": c["p50_us"],
        "p99_us": c["p99_us"],
        "kv_pages_high_water": c["pool"]["high_water"],
        "storm_fast_p99_us": s.get("fast", {}).get("p99_us"),
        "storm_interference_p99_ratio": s.get("interference_p99_ratio"),
        "storm_shed_total": s.get("shed_total"),
        "storm_slow_tokens_s": s.get("slow", {}).get("tokens_s"),
        # ISSUE-14 availability columns: the router storm (1-of-2
        # replicas killed mid-storm) — dropped must stay 0
        "router_storm": c.get("router_storm"),
        "compile_s": c["compile_s"],
        "cache_hits": c["cache_hits"],
        "cache_misses": c["cache_misses"],
        "telemetry": c.get("telemetry"),
        "platform": c["platform"],
    }
    return _stamp_fleet_telemetry(lane, tel_dir)


def lane_pipeline(on_cpu: bool) -> dict:
    """Async pipeline engine lane (PR 5): runs
    benchmark/pipeline_latency.py's sync-vs-pipelined A/B and carries its
    counters into lanes[].  The value is the pipelined loop's
    ``device_idle_gap_us`` — mean per-step host time OUTSIDE the dispatch
    phase, the window the one-program-per-step device can run dry.  The
    acceptance bars ride along: steady-state dispatch-ahead depth >= 2,
    idle gap reduced vs the synchronous loop, 0 blocking host syncs per
    pipelined step (counter-based: a direct ``--lane`` run under
    JAX_PLATFORMS=cpu checks them too)."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "pipeline_latency.py")
    r = subprocess.run([sys.executable, "-u", script, "--json"],
                       capture_output=True, text=True,
                       timeout=600, env=dict(os.environ))
    if r.returncode != 0:
        raise RuntimeError(f"pipeline lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])["pipeline"]
    _progress(f"pipeline: idle gap {c['device_idle_gap_us']:.0f} us/step "
              f"(sync {c['device_idle_gap_us_sync']:.0f}), ahead depth "
              f"{c['steady_ahead_depth']}, "
              f"{c['pipelined']['host_syncs_per_step']} syncs/step")
    return {
        "metric": "pipeline_device_idle_gap_us",
        "value": c["device_idle_gap_us"],
        "unit": "us/step",
        "vs_baseline": 0.0,
        "device_idle_gap_us_sync": c["device_idle_gap_us_sync"],
        "idle_gap_reduction": c["idle_gap_reduction"],
        "steady_ahead_depth": c["steady_ahead_depth"],
        "host_syncs_per_step": c["pipelined"]["host_syncs_per_step"],
        "wall_speedup": c["wall_speedup"],
        "compiled": c["pipelined"]["compiled"],
        "telemetry": c.get("telemetry"),
        "compile_s": c["compile_s"],
        "cache_hits": c["cache_hits"],
        "cache_misses": c["cache_misses"],
        "platform": c["platform"],
    }


def lane_multichip(on_cpu: bool) -> dict:
    """Pod-scale SPMD lane (kvstore='tpu' mesh sharding): runs
    benchmark/multichip_scaling.py's 1->N weak-scaling sweep and carries
    the curve into lanes[].  The value is img/s/chip at the FULL mesh;
    the curve (img/s/chip + step-time variance per mesh size) replaces
    the bare device probe MULTICHIP_r0x.json carried since PR 1.  On CPU
    the virtual 8-device world measures the same partitioned program
    (honest ``platform`` either way); per-lane counters assert 1 compiled
    launch/step and 0 steady-state reshards."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "multichip_scaling.py")
    env, tel_dir = _fleet_telemetry_env("multichip")
    if on_cpu:
        env.setdefault("MULTICHIP_PER_CHIP", "16")
        env.setdefault("MULTICHIP_STEPS", "10")
    r = subprocess.run([sys.executable, "-u", script, "--json"],
                       capture_output=True, text=True,
                       timeout=600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"multichip lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])
    _progress(f"multichip: {c['n_devices']} devices, "
              f"{c['value']:.0f} img/s/chip at full mesh, "
              f"efficiency {c['scaling_efficiency']:.2f}, "
              f"curve {[round(l['img_s_per_chip']) for l in c['curve']]}")
    c["vs_baseline"] = 0.0
    return _stamp_fleet_telemetry(c, tel_dir)


def lane_moe(on_cpu: bool) -> dict:
    """Expert-parallel MoE lane (ISSUE 20): runs
    benchmark/multichip_scaling.py --moe — an MoEBlock under
    MXNET_SPMD_MESH='ep=4,dp=2' with the load-balance aux head folded
    into the one donated step.  The value is routed tokens/s/chip;
    capacity-drop counters and the ``moe.*`` telemetry gauges ride
    along so check_perf_delta defends throughput AND drop rate."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "multichip_scaling.py")
    env, tel_dir = _fleet_telemetry_env("moe")
    if on_cpu:
        env.setdefault("MULTICHIP_STEPS", "10")
    r = subprocess.run([sys.executable, "-u", script, "--moe", "--json"],
                       capture_output=True, text=True,
                       timeout=600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"moe lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])
    if c.get("skipped"):
        _progress(f"moe: SKIPPED ({c['skipped']})")
    else:
        _progress(f"moe: {c['value']:.0f} tokens/s/chip, "
                  f"{c['launches_per_step']:.1f} launches/step, "
                  f"{c['dropped_slots']}/{c['routed_slots']} dropped")
    c["vs_baseline"] = 0.0
    return _stamp_fleet_telemetry(c, tel_dir)


def lane_pp(on_cpu: bool) -> dict:
    """Pipeline-parallel lane (ISSUE 20): runs
    benchmark/multichip_scaling.py --pp — a 2-stage PipelineBlock on
    the pp=2,dp=2,fsdp=2 mesh at two microbatch counts.  The value is
    the MEASURED bubble fraction (fill/drain share of step time from
    the T(M) = A + B/M slope fit) next to the GPipe closed form; step
    time and the ``pp.*`` gauges ride along for check_perf_delta."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "multichip_scaling.py")
    env, tel_dir = _fleet_telemetry_env("pp")
    if on_cpu:
        env.setdefault("MULTICHIP_STEPS", "10")
    r = subprocess.run([sys.executable, "-u", script, "--pp", "--json"],
                       capture_output=True, text=True,
                       timeout=600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"pp lane failed:\n{r.stderr[-1500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])
    if c.get("skipped"):
        _progress(f"pp: SKIPPED ({c['skipped']})")
    else:
        _progress(f"pp: bubble {c['value']:.2f} measured / "
                  f"{c['bubble_fraction_theoretical']:.2f} theoretical, "
                  f"{c['step_ms_mean']:.2f} ms/step, "
                  f"{c['launches_per_step']:.1f} launches/step")
    c["vs_baseline"] = 0.0
    return _stamp_fleet_telemetry(c, tel_dir)


def lane_elastic(on_cpu: bool) -> dict:
    """Elastic-recovery lane (drill-driven, ROADMAP 4c): runs
    benchmark/elastic_drill.py's sigterm_drain drill — a real SIGTERM
    mid compiled-SPMD-step with async checkpointing, then a restart
    warm-started from the persistent compile cache — and carries the
    recovery-time budget into lanes[].  The value is recovery_wall_s
    (restart process start -> first resumed step); steps_replayed,
    drain_s, and the restart's disk hits / fresh compiles ride along.
    The drill children always run the CPU virtual mesh (recovery
    SEMANTICS are platform-independent; on-chip recovery seconds come
    from the same drill run against a TPU cache dir)."""
    import json as _json

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmark", "elastic_drill.py")
    # the drill children inherit MXNET_TELEMETRY_DIR through
    # drills._child_env, so the fleet merge below folds the killed and
    # restarted children's shards, not just the orchestrator's
    env, tel_dir = _fleet_telemetry_env("elastic")
    r = subprocess.run([sys.executable, "-u", script, "--json"],
                       capture_output=True, text=True,
                       timeout=600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"elastic lane failed:\n{r.stderr[-1500:]}\n"
                           f"{r.stdout[-500:]}")
    c = _json.loads(r.stdout.strip().splitlines()[-1])["elastic"]
    _progress(f"elastic: recovery {c['recovery_wall_s']:.2f}s wall "
              f"({c['recovery_s']*1e3:.1f}ms restore), "
              f"{c['steps_replayed']} replayed, drain "
              f"{c['drain_s']*1e3:.1f}ms, {c['fresh_compiles']} fresh "
              f"compiles / {c['disk_hits']} disk hits on restart, "
              f"sentinel overhead {c.get('sentinel_overhead_pct')}%")
    lane = {
        "metric": "elastic_recovery_wall_s",
        "value": c["recovery_wall_s"],
        "unit": "s",
        "vs_baseline": 0.0,
        "scenario": c["scenario"],
        "recovery_s": c["recovery_s"],
        "steps_replayed": c["steps_replayed"],
        "drain_s": c["drain_s"],
        "fresh_compiles": c["fresh_compiles"],
        "disk_hits": c["disk_hits"],
        "restored_at": c["restored_at"],
        "exit_code_c1": c["exit_code_c1"],
        # ISSUE-13 training-integrity sentinel A/B (cadence 20 vs off
        # on the drill train step; acceptance < 1% evaluated on-chip)
        "sentinel_overhead_pct": c.get("sentinel_overhead_pct"),
        "sentinel_ab": c.get("sentinel_ab"),
        "telemetry": c.get("telemetry"),
        "platform": c["platform"],
    }
    return _stamp_fleet_telemetry(lane, tel_dir)


def _resolve_lane(name):
    """Lane key -> (callable(on_cpu) -> lane dict, metric name).  Any model
    zoo name works, with optional _bf16 / _int8 suffixes."""
    if name == "bert":
        return lane_bert, "bert_base_train_throughput_per_chip"
    if name == "train_step":
        return lane_train_step, "train_step_compiled_dispatches_per_step"
    if name == "infer":
        return lane_infer, "serving_infer_p99_latency_us"
    if name == "decode":
        return lane_decode, "decode_continuous_tokens_per_s"
    if name == "pipeline":
        return lane_pipeline, "pipeline_device_idle_gap_us"
    if name == "multichip":
        return lane_multichip, "multichip_img_s_per_chip"
    if name == "moe":
        return lane_moe, "moe_tokens_per_s_per_chip"
    if name == "pp":
        return lane_pp, "pp_bubble_fraction"
    if name == "elastic":
        return lane_elastic, "elastic_recovery_wall_s"
    if name.endswith("_int8"):
        model = name[: -len("_int8")] or "resnet50_v1"
        return (lambda on_cpu, m=model: lane_int8(on_cpu, m),
                f"{model}_int8_infer_throughput_per_chip")
    if name.endswith("_bf16"):
        model = name[: -len("_bf16")] or "resnet50_v1"
        return (lambda on_cpu, m=model: lane_train(on_cpu, True, m),
                f"{model}_bf16_train_throughput_per_chip")
    return (lambda on_cpu, m=name: lane_train(on_cpu, False, m),
            f"{name}_train_throughput_per_chip")


# Ordering: bf16 resnet first (the headline AND the cheapest real-model
# compile — its XLA program also warms the compile cache for fp32); int8
# last (longest end-to-end: calibration + conversion + compile).
LANE_ORDER = ["resnet50_v1_bf16", "resnet50_v1", "bert", "train_step",
              "infer", "decode", "pipeline", "multichip", "moe", "pp",
              "elastic", "resnet50_v1_int8"]

# generous-but-bounded per-lane wall budgets (seconds).
# BENCH_LANE_TIMEOUT overrides every budget.
_LANE_BUDGET = {"resnet50_v1_bf16": 600.0, "resnet50_v1": 600.0,
                "bert": 540.0, "train_step": 240.0, "infer": 240.0,
                "decode": 300.0, "pipeline": 240.0, "multichip": 420.0,
                "moe": 240.0, "pp": 300.0,
                "elastic": 300.0, "resnet50_v1_int8": 900.0}

# lanes whose body starts a worker subprocess that needs the chip: their
# lane child must never open the backend itself
_SUBPROCESS_LANES = frozenset({"train_step", "infer", "decode", "pipeline",
                               "multichip", "moe", "pp", "elastic"})


def _lane_budget(name: str) -> float:
    override = os.environ.get("BENCH_LANE_TIMEOUT")
    if override:
        try:
            return float(override)
        except ValueError:
            pass
    return _LANE_BUDGET.get(name, 600.0)


def _run_lane_child(name: str) -> None:
    """Child mode (``bench.py --lane NAME``): run ONE lane and print its
    lane dict as the only stdout line.  ``on_cpu`` (lane sizes) comes
    from the environment, never from jax: a subprocess-backed lane's
    worker is the only process allowed to open the chip.  EVERYTHING
    stays inside the try: an escape to the __main__ handler would emit
    the orchestrator-shaped payload on our stdout, which the parent would
    record as the lane result under the wrong metric."""
    try:
        _, metric = _resolve_lane(name)
    except Exception:
        metric = f"{name}_train_throughput_per_chip"
    unit = "tokens/s" if name == "bert" else "img/s"
    try:
        on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        fn, metric = _resolve_lane(name)
        if name in _SUBPROCESS_LANES:
            # compile_s / cache_hits / cache_misses / telemetry are the
            # WORKER's, already in its JSON
            lane = fn(on_cpu)
        else:
            from mxnet_tpu import program_store as _ps
            from mxnet_tpu import telemetry as _tel

            _ps.enable_persistent_cache(min_compile_secs=5)
            lane = fn(on_cpu)
            # every in-process lane carries the cold-start counters
            # (lanes that time their own compile keep their number) and
            # the full namespaced telemetry snapshot of this process
            disk = _ps.disk_stats()
            lane.setdefault("compile_s", round(_ps.compile_seconds(), 1))
            lane.setdefault("cache_hits", disk["hits"])
            lane.setdefault("cache_misses", disk["misses"])
            if lane.get("telemetry") is None:
                lane["telemetry"] = {k: v for k, v in
                                     _tel.snapshot().items() if v}
    except BaseException:
        tb = traceback.format_exc()
        _progress(f"lane {name} FAILED:\n" + tb)
        lane = {"metric": metric, "value": 0.0, "unit": unit,
                "vs_baseline": 0.0,
                "error": tb.strip().splitlines()[-1][:400]}
        print(json.dumps(lane), flush=True)
        os._exit(1)                      # never reach the __main__ handler
    print(json.dumps(lane), flush=True)
    os._exit(0)


def _error_lane(name: str, metric: str, error: str) -> dict:
    return {"metric": metric, "value": 0.0,
            "unit": "tokens/s" if name == "bert" else "img/s",
            "vs_baseline": 0.0, "error": error}


def _spawn_lane(name: str, budget: float, metric: str) -> dict:
    """Run one lane in a subprocess with a hard wall budget; returns its
    lane dict (or an error lane on timeout/crash)."""
    env = dict(os.environ)
    # the child must never touch the parent's partial file or its .done
    # watchdog stand-down marker
    env.pop("BENCH_PARTIAL_PATH", None)
    _progress(f"lane {name}: spawning (budget {budget:.0f}s)")
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--lane", name],
            env=env, capture_output=True, text=True, timeout=budget)
    except subprocess.TimeoutExpired as e:
        if e.stderr:      # the stall point (compile? warm? timed loop?)
            err = e.stderr
            sys.stderr.write(err.decode("utf-8", "replace")
                             if isinstance(err, bytes) else err)
        _progress(f"lane {name}: KILLED after {budget:.0f}s budget")
        # a lane may print a preliminary result line before an optional
        # enrichment phase (lane_int8 does, ahead of its bf16 reference);
        # the measurement that completed should survive the kill
        out = e.stdout
        if isinstance(out, bytes):
            out = out.decode("utf-8", "replace")
        for line in reversed((out or "").strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    lane = json.loads(line)
                    lane["truncated"] = f"budget {budget:.0f}s"
                    _progress(f"lane {name}: kept its preliminary result")
                    return lane
                except ValueError:
                    continue
        return _error_lane(name, metric,
                           f"lane exceeded {budget:.0f}s budget")
    sys.stderr.write(r.stderr)           # lane progress, verbatim
    for line in reversed(r.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                lane = json.loads(line)
            except ValueError:
                continue
            # a child that died (OOM-kill, segfault) after printing a
            # preliminary line must not read as a clean lane; the error
            # path prints its own lane with "error" set and exits 1
            if r.returncode != 0 and "error" not in lane:
                lane["truncated"] = f"rc={r.returncode}"
            return lane
    _progress(f"lane {name}: no JSON on child stdout (rc={r.returncode})")
    return _error_lane(name, metric,
                       f"lane subprocess rc={r.returncode}, no result line")


def _record(lane: dict) -> None:
    if lane.get("value", 0) > 0 and not _FIRST_RESULT_T:
        _FIRST_RESULT_T.append(time.time())
    _LANES.append(lane)
    with open(_PARTIAL_PATH, "a") as f:       # the watchdog's view
        f.write(json.dumps(lane) + "\n")


def main():
    if "--lane" in sys.argv:
        _run_lane_child(sys.argv[sys.argv.index("--lane") + 1])
        return

    timeout_s = float(os.environ.get("BENCH_TIMEOUT", "2700"))
    deadline = _T0 + timeout_s
    _watchdog(timeout_s)

    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT", "120"))
    model = os.environ.get("BENCH_MODEL", "all")
    selected = LANE_ORDER if model == "all" else [model]

    # The parent NEVER imports jax: probing and lane execution live in
    # subprocesses that run one at a time, so exactly one process holds
    # the chip at any moment.
    failed = 0
    probe = None                      # (ok, platform): probed ONCE a run
    for name in selected:
        _, metric = _resolve_lane(name)
        remaining = deadline - time.time() - 90.0     # margin for emit
        if remaining < 120.0:
            _progress(f"lane {name}: skipped ({remaining:.0f}s left)")
            _record(_error_lane(name, metric,
                                "window exhausted before lane started"))
            failed += 1
            continue
        if probe is None:
            probe = _probe_device_backend(
                min(probe_timeout, max(remaining / 4, 30.0)))
            # the probe may have burned its whole timeout — recompute, or
            # the last lane can overshoot the deadline into the watchdog
            remaining = deadline - time.time() - 90.0
        probe_ok, platform = probe
        if probe_ok and platform != "cpu":
            lane = _spawn_lane(name, min(_lane_budget(name), remaining),
                               metric)
        else:
            # no chip, no number: a CPU run is never written under the
            # name of a device metric
            lane = _error_lane(
                name, metric,
                "no accelerator: device probe "
                + (f"found platform {platform!r}" if probe_ok
                   else "failed"))
        _record(lane)
        if lane.get("value", 0) <= 0:
            failed += 1

    _emit_final()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException:
        tb = traceback.format_exc()
        _progress("FATAL:\n" + tb)
        _emit_final(error=tb.strip().splitlines()[-1])
        sys.exit(1)
