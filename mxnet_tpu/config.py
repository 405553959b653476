"""Typed environment-variable configuration registry.

Reference: the ~102 documented ``MXNET_*`` env vars read via
``dmlc::GetEnv`` at point of use (docs/static_site/.../env_var.md) plus
the dmlc ``Parameter``/``DMLC_DECLARE_FIELD`` reflection that gives each
knob a type, default, bounds, and docstring.  Here both roles live in one
registry: every knob is declared once with type/default/validator/doc,
reads go through :func:`get` (validated, cached), and
:func:`describe`/:func:`to_markdown` generate the env-var table the
reference maintained by hand.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

__all__ = ["declare", "get", "describe", "to_markdown", "refresh",
           "VARIABLES"]


@dataclass
class EnvVar:
    name: str
    type: Callable
    default: Any
    doc: str
    validator: Optional[Callable[[Any], bool]] = None
    subsystem: str = "core"
    # cached=False: re-read the environment on every get().  For knobs that
    # tests/tools legitimately flip mid-process (paths, debug switches).
    cached: bool = True


VARIABLES: Dict[str, EnvVar] = {}
_CACHE: Dict[str, Any] = {}


def declare(name: str, type: Callable = str, default: Any = None,
            doc: str = "", validator: Optional[Callable] = None,
            subsystem: str = "core", cached: bool = True) -> EnvVar:
    """Register a knob (DMLC_DECLARE_FIELD analog).  Idempotent by name."""
    if name in VARIABLES:
        return VARIABLES[name]
    v = EnvVar(name, type, default, doc, validator, subsystem, cached)
    VARIABLES[name] = v
    return v


def _parse(var: EnvVar, raw: str) -> Any:
    if var.type is bool:
        val = raw.strip().lower() in ("1", "true", "yes", "on")
    else:
        val = var.type(raw)
    if var.validator is not None and not var.validator(val):
        raise ValueError(
            f"{var.name}={raw!r} failed validation ({var.doc})")
    return val


def get(name: str, default: Any = None) -> Any:
    """Validated, cached env read (dmlc::GetEnv analog).  Unknown names
    raise — every knob must be declared.  Only values parsed from the
    environment are cached: a call-site ``default`` applies to that call
    alone and must never shadow the declared default for other callers."""
    if name not in VARIABLES:
        raise KeyError(f"undeclared env var {name}; declare() it first")
    if name in _CACHE:
        return _CACHE[name]
    var = VARIABLES[name]
    raw = os.environ.get(name)
    if raw is None:
        val = var.default if default is None else default
        if (default is not None and var.validator is not None
                and not var.validator(val)):
            raise ValueError(
                f"{name} call-site default {val!r} failed validation "
                f"({var.doc})")
        return val
    val = _parse(var, raw)
    if var.cached:
        _CACHE[name] = val
    return val


def refresh(name: Optional[str] = None) -> None:
    """Drop cached reads (tests / runtime re-configuration)."""
    if name is None:
        _CACHE.clear()
    else:
        _CACHE.pop(name, None)


def describe() -> Dict[str, Dict[str, Any]]:
    return {
        n: {"type": v.type.__name__, "default": v.default, "doc": v.doc,
            "subsystem": v.subsystem}
        for n, v in sorted(VARIABLES.items())
    }


def to_markdown() -> str:
    """Generate the env-var reference table (the reference's
    faq/env_var.md, but produced from the registry so it can't go
    stale)."""
    lines = ["# Environment variables", "",
             "Generated from `mxnet_tpu.config.VARIABLES` "
             "(`python -c \"import mxnet_tpu.config as c; "
             "print(c.to_markdown())\"`).", ""]
    by_sub: Dict[str, list] = {}
    for v in VARIABLES.values():
        by_sub.setdefault(v.subsystem, []).append(v)
    for sub in sorted(by_sub):
        lines.append(f"## {sub}")
        lines.append("")
        lines.append("| Variable | Type | Default | Description |")
        lines.append("|---|---|---|---|")
        for v in sorted(by_sub[sub], key=lambda x: x.name):
            lines.append(f"| `{v.name}` | {v.type.__name__} | "
                         f"`{v.default}` | {v.doc} |")
        lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Declarations: the knobs this framework reads (reference env_var.md table)
# ---------------------------------------------------------------------------

declare("MXNET_HOME", str, "~/.mxnet",
        "Cache root for model-zoo checkpoints and datasets",
        subsystem="io", cached=False)
declare("MXNET_SKIP_SHA1_CHECK", bool, False,
        "Accept cached pretrained checkpoints without checksum "
        "verification", subsystem="io")
declare("MXNET_CPU_WORKER_NTHREADS", int, 4,
        "Host-side worker threads for IO prefetch / native engine "
        "(reference engine env var of the same name)",
        validator=lambda v: v >= 1, subsystem="engine")
declare("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
        "Engine facade selection; XLA async dispatch is the real "
        "scheduler, NaiveEngine forces synchronous eager dispatch for "
        "debugging (reference MXNET_ENGINE_TYPE)", subsystem="engine",
        cached=False)
declare("MXNET_BACKWARD_DO_MIRROR", bool, False,
        "Rematerialize forwards during backward (jax.checkpoint) instead "
        "of keeping activations alive — trades ~1 extra forward of FLOPs "
        "for peak HBM (reference mirror path, src/nnvm/gradient.cc); "
        "per-net override: hybridize(remat=...)", subsystem="memory")
declare("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
        "Arrays larger than this many elements get their own dist push "
        "bucket (reference kvstore_dist big-array splitting)",
        validator=lambda v: v > 0, subsystem="kvstore")
declare("MXNET_SPMD_MESH", str, "auto",
        "SPMD mesh for kvstore='tpu' (cached_step.TrainStep traces under "
        "it; all collectives scheduled by the XLA partitioner inside the "
        "one donated program).  'auto' = every visible device on 'dp' "
        "(single-device worlds stay on the plain single-chip path); an "
        "integer = that many devices on 'dp'; '0'/'off' disables; "
        "'dp=4,fsdp=2' axis specs go through parallel.mesh.make_mesh — "
        "the batch shards over 'dp' only, an 'fsdp' axis shards params + "
        "optimizer state (ZeRO-3 style, spmd.param_spec), and a 'tp' "
        "axis carries model-code sharding.constraint annotations.",
        subsystem="kvstore", cached=False)
declare("MXNET_FSDP_MIN_SIZE", int, 1024,
        "FSDP sharding floor (spmd.param_spec): parameter/optimizer-"
        "state leaves with fewer elements than this stay replicated on "
        "an 'fsdp' mesh axis — sharding a LayerNorm bias buys no memory "
        "and costs an all-gather.",
        validator=lambda v: v >= 0, subsystem="kvstore", cached=False)
declare("MXNET_MOE_AUX_WEIGHT", float, 0.01,
        "Weight on the MoE load-balance auxiliary loss "
        "(parallel.moe.MoEBlock records the Shazeer balance penalty into "
        "moe.aux_scope; cached_step.TrainStep folds weight*sum(aux) into "
        "the differentiated loss heads on both the compiled and eager "
        "paths, so the penalty reaches the optimizer without widening "
        "the user loss_fn contract).  0 disables the fold.",
        validator=lambda v: v >= 0, subsystem="kvstore", cached=False)
declare("MXNET_ENGINE_PREFETCH", int, 2,
        "Async pipeline engine: device-prefetch depth — how many batches "
        "a DevicePrefetcher transfer thread stages into HBM ahead of the "
        "consuming step (engine.prefetch / DataLoader(device_prefetch=)). "
        "0 disables the stage (synchronous per-batch device_put); "
        "MXNET_ENGINE_TYPE=NaiveEngine forces 0.",
        validator=lambda v: v >= 0, subsystem="engine", cached=False)
declare("MXNET_AMP_LAG", int, 1,
        "Deferred AMP gate lag window (cached_step.TrainStep): 1 = read "
        "step N-1's all-finite flag while dispatching step N — the step "
        "dispatches speculatively with both scale candidates and the "
        "device selects via the previous flag, so the read never blocks "
        "on the current program and numerics stay bit-exact vs the "
        "synchronous gate.  0 = synchronous read (the PR-3 behavior); "
        "values > 1 clamp to 1 (one unread flag is the whole speculation "
        "budget).  MXNET_ENGINE_TYPE=NaiveEngine forces 0.",
        validator=lambda v: v >= 0, subsystem="engine", cached=False)
declare("MXNET_METRIC_DEVICE", int, 1,
        "Device-side metric accumulators: EvalMetric.update on device "
        "NDArrays enqueues a compiled accumulate (no per-batch host "
        "sync); the host read happens at .get()/engine.waitall() or "
        "every MXNET_METRIC_SYNC_STEPS updates.  0 = host accumulation "
        "everywhere (each update counted in metric.host_sync_count); "
        "MXNET_ENGINE_TYPE=NaiveEngine forces 0.",
        subsystem="engine", cached=False)
declare("MXNET_METRIC_SYNC_STEPS", int, 50,
        "Device-side metric accumulators: fold the device scalars into "
        "the host sums every N update() calls — bounds both the async "
        "queue the accumulator keeps in flight and f32 accumulation "
        "error", validator=lambda v: v >= 1, subsystem="engine",
        cached=False)
declare("MXNET_ENFORCE_DETERMINISM", bool, False,
        "Disable nondeterministic optimizations (XLA autotuning picks "
        "deterministic kernels)", subsystem="engine")
declare("MXNET_EAGER_JIT", int, 1,
        "Per-op jit compilation cache for eager dispatch (the reference "
        "engine's operator-bulking analog): one cached XLA executable per "
        "(op, attrs) instead of per-primitive device round-trips.  0 = "
        "off, 1 = on for the TPU backend (default; CPU eager stays plain "
        "dispatch), 2 = force everywhere (tests/benchmarks).")
declare("MXNET_FUSED_OPTIMIZER", int, 1,
        "Fused multi-tensor optimizer step for the eager Trainer/KVStore "
        "path: parameters group by (dtype, hyper-param signature, "
        "multi-precision) and each group updates as ONE jit-compiled, "
        "buffer-donated program (optimizer/fused.py) — ~1 dispatch per "
        "group instead of 1+ per parameter.  1 = on (default; optimizers "
        "without a fused_update rule fall back to the scalar loop "
        "per-parameter), 0 = force the scalar loop everywhere.",
        subsystem="optimizer", cached=False)
declare("MXNET_COMPILED_STEP", int, 1,
        "Compiled whole-train-step (cached_step.TrainStep via "
        "Trainer.compile_step): loss-fn forward, vjp backward, gradient "
        "reduce, the fused optimizer update, and the AMP all-finite gate "
        "trace into ONE jit-compiled program with donated parameter/"
        "optimizer-state buffers, cached by (input shapes/dtypes, "
        "train-mode, hyper-param signature) like the reference CachedOp's "
        "shape-keyed graph cache — 1 device dispatch per step (+1 host "
        "scalar read with AMP).  1 = on (default; ineligible setups fall "
        "back to the eager tape transparently), 0 = force the eager tape "
        "everywhere.", subsystem="optimizer", cached=False)
declare("MXNET_COMPILED_STEP_CACHE", int, 16,
        "Per-TrainStep cap of the ProgramStore 'train_step' namespace "
        "(LRU over input-shape signatures); a new signature past the cap "
        "evicts the oldest.  MXNET_PROGRAM_CACHE_CAPS overrides it.",
        validator=lambda v: v > 0, subsystem="optimizer",
        cached=False)
declare("MXNET_PROGRAM_CACHE_DIR", str, None,
        "ProgramStore persistent compilation cache: when set, every XLA "
        "compile this process performs is backed by JAX's on-disk cache "
        "at this path, keyed by (serialized HLO, compile options, "
        "jax/jaxlib version) — a second process re-tracing the same "
        "signature gets a disk hit (seconds) instead of a fresh compile "
        "(26-98 s/program on chip).  Off by default (unset = purely "
        "in-memory, prior behavior).  Never overrides an externally "
        "configured JAX_COMPILATION_CACHE_DIR.  A corrupted/unreadable "
        "entry degrades loudly to a recompile (fault site "
        "program_store.load), never a crash.",
        subsystem="program_store", cached=False)
declare("MXNET_PROGRAM_CACHE_CAPS", str, "",
        "Per-namespace program-cap overrides for the ProgramStore, as a "
        "comma list 'train_step=16,serving=32,hybrid_forward=32,"
        "eager_jit=512'.  Unlisted namespaces fall back to their legacy "
        "knob (MXNET_COMPILED_STEP_CACHE, MXNET_FORWARD_CACHE) or "
        "built-in default.  Caps bound programs PER OWNER (per "
        "TrainStep / ServingEngine / HybridBlock), so co-hosted models "
        "cannot evict each other's steady-state programs.",
        subsystem="program_store", cached=False)
declare("MXNET_PROGRAM_AOT", int, 1,
        "ProgramStore ahead-of-time executables: 1 = a cache miss "
        "traces AND compiles before first dispatch "
        "(jit(...).lower(args).compile()) and the store owns the "
        "compiled executable — warm-up from abstract shapes "
        "(Trainer.precompile / ServingEngine.warmup), steady state, and "
        "elastic restore share one code path; an input-signature "
        "mismatch at dispatch falls back loudly to the retraceable jit "
        "callable (aot_fallbacks counter).  0 = records keep only the "
        "jit callable (pre-PR-7 dispatch behavior).",
        subsystem="program_store", cached=False)
declare("MXNET_EAGER_JIT_EXCLUDE", str, "mean,sum,prod,max,min",
        "Comma-set of op names kept OUT of the per-op eager jit cache "
        "(MXNET_EAGER_JIT): single-primitive reductions measured SLOWER "
        "jitted than plain dispatch (mean(axis) 0.62x, measured through "
        "the old remote relay; ROADMAP D6 still owes a run on a directly "
        "attached chip — one primitive is already one dispatch, so the "
        "cache only adds lookup overhead).  Override with your own list; "
        "empty string re-admits every op.", cached=False)
declare("MXNET_PAD_CHANNELS", int, 1,
        "MXU-alignment padding pass for staged convolutions (ops/nn.py "
        "Convolution, trace-time only): channel axes that miss the TPU "
        "tile quanta (8-lane sublane quantum for fp32/bf16, 32 for int8) "
        "zero-pad up to the quantum inside the traced program — Cin pads "
        "on both operands (exact: padded taps contribute 0.0), Cout pads "
        "and slices back (exact: output channels are independent dots) — "
        "so misaligned convs (the cin=3 stem, odd-channel heads) stop "
        "underfilling the MXU.  The pad/slice live INSIDE the program, "
        "keyed by the unpadded shapes: 0 added retraces or dispatches "
        "per step.  Bit-exactness is asserted by "
        "tests/test_pad_channels.py.  1 = on for TPU staging "
        "(default), 0 = off, 2 = force on every backend (tests/CI).",
        validator=lambda v: v in (0, 1, 2))
declare("MXNET_BN_TWO_PASS_VAR", bool, False,
        "BatchNorm batch variance via the two-pass shifted formula instead "
        "of the single-pass E[x^2]-E[x]^2 TPU default (one extra HBM pass; "
        "use when activation |mean| >> std makes the single-pass cancel)",
        subsystem="operator")
declare("MXNET_FAULT_PLAN", str, None,
        "Deterministic fault-injection plan for subprocess tests: "
        "'site[@after]:times[:kind]' comma-list (kind: transient|fatal|"
        "oserror|timeout) installed at import (faults.FaultPlan.from_env). "
        "Unset = injection disabled (faults.inject is a no-op None check).",
        subsystem="faults", cached=False)
declare("MXNET_BARRIER_TIMEOUT", float, 0.0,
        "KVStore.barrier() deadline in seconds; on breach the barrier "
        "raises faults.DeadlineExceeded naming suspected-dead ranks from "
        "the attached HeartbeatMonitor.  0 = wait forever (reference "
        "behavior).", validator=lambda v: v >= 0, subsystem="faults",
        cached=False)
declare("MXNET_RETRY_MAX", int, 3,
        "faults.retry_call default: max re-attempts after the first try "
        "(total attempts = value + 1) for retryable failures",
        validator=lambda v: v >= 0, subsystem="faults", cached=False)
declare("MXNET_RETRY_BACKOFF", float, 0.05,
        "faults.retry_call default: base delay (s) of the deterministic "
        "exponential backoff min(backoff * 2**(attempt-1), max)",
        validator=lambda v: v >= 0, subsystem="faults", cached=False)
declare("MXNET_RETRY_BACKOFF_MAX", float, 2.0,
        "faults.retry_call default: backoff delay cap in seconds",
        validator=lambda v: v >= 0, subsystem="faults", cached=False)
declare("MXNET_DATALOADER_RETRIES", int, 2,
        "DataLoader: per-batch recovery budget — a crashed worker pool is "
        "respawned and the batch re-fetched up to this many times before "
        "DataLoaderWorkerError raises with the batch index and worker id",
        validator=lambda v: v >= 0, subsystem="faults", cached=False)
declare("MXNET_DOWNLOAD_RETRIES", int, 3,
        "model_store.download: re-attempts after the first try; every "
        "attempt removes partial files on failure and re-verifies sha1",
        validator=lambda v: v >= 0, subsystem="faults", cached=False)
declare("MXNET_ELASTIC_BACKOFF", float, 0.0,
        "run_elastic: base delay (s) of the exponential backoff between "
        "restore-and-resume restarts (capped at MXNET_RETRY_BACKOFF_MAX); "
        "0 = restart immediately", validator=lambda v: v >= 0,
        subsystem="faults", cached=False)
declare("MXNET_PREEMPTION_GRACE_S", float, 30.0,
        "Preemption-notice grace budget (seconds): after SIGTERM/SIGINT "
        "the preemption handler (preemption.install) stops admission, "
        "drains every async queue (engine.waitall: prefetch, deferred "
        "AMP, device metrics, checkpoint writers, serving/decode "
        "queues), forces a final blocking checkpoint, and exits with "
        "MXNET_PREEMPTION_EXIT_CODE — a watchdog force-exits if the "
        "drain has not finished inside this budget (a pod scheduler's "
        "SIGKILL would anyway).  0 = no watchdog (drain may take as "
        "long as it takes).", validator=lambda v: v >= 0,
        subsystem="faults", cached=False)
declare("MXNET_PREEMPTION_EXIT_CODE", int, 83,
        "Exit code of a SUCCESSFUL graceful preemption drain (flag -> "
        "waitall -> final blocking checkpoint): a supervisor/drill "
        "seeing this code knows the newest checkpoint is the exact "
        "pre-signal state and restart-and-replay loses zero steps.  A "
        "drain that FAILED exits 1 instead (never trust the "
        "distinguished code after a failed drain); the watchdog "
        "force-exit uses this code + 1.",
        validator=lambda v: 1 <= v <= 120, subsystem="faults",
        cached=False)
declare("MXNET_SENTINEL_EVERY", int, 20,
        "Training-integrity sentinel cadence (mxnet_tpu/sentinel.py): "
        "every N compiled train-step dispatches the donated program "
        "additionally emits an on-device state fingerprint (uint32 "
        "bitcast fold over post-update params + optimizer state, plus "
        "float param-sum / grad-norm signals) behind an in-program "
        "lax.cond — 0 extra dispatches, 0 retraces; the host read is "
        "deferred a full cadence (or forced at checkpoint boundaries). "
        "Per-replica digest shards are voted for silent corruption "
        "under kvstore='tpu'.  0 = sentinel off (no digest reads; the "
        "cond branch never executes).",
        validator=lambda v: v >= 0, subsystem="faults", cached=False)
declare("MXNET_SENTINEL_ZMAX", float, 6.0,
        "Sentinel anomaly window z-score threshold: a grad-norm (or "
        "observed-loss) sample farther than zmax standard deviations "
        "from its EMA — or any non-finite sample, the old "
        "nonfinite_anomaly — trips the windowed detector and rolls the "
        "elastic loop back to the last digest-verified checkpoint "
        "(fault site sentinel.rollback).",
        validator=lambda v: v > 0, subsystem="faults", cached=False)
declare("MXNET_SENTINEL_STRIKES", int, 1,
        "Replica divergences a device may accumulate before the "
        "sentinel quarantines it (persisted quarantine.json consumed "
        "by parallel.spmd.resolve_mesh on the next restart — the mesh "
        "re-resolves WITHOUT the suspect device).  1 = first confirmed "
        "corruption quarantines immediately.",
        validator=lambda v: v >= 1, subsystem="faults", cached=False)
declare("MXNET_SHAPE_BUCKETS", str, "pow2",
        "Shape-bucket grid for padded compilation (serving.BucketPolicy): "
        "'pow2' (default — round a dynamic axis up to the next power of "
        "two), 'none' (exact shapes, bucketing off), or an explicit "
        "ascending comma list '8,16,32,64' (a length above the largest "
        "bucket falls back to the exact shape).  Used by ServingEngine "
        "always; by Trainer.compile_step(bucket=True) and "
        "hybridize(bucket=True) on opt-in.  Padded results are verified "
        "bit-exact vs the unpadded eager path once per bucket and "
        "bucketing is REFUSED (sticky, reason recorded) on mismatch.",
        subsystem="serving", cached=False)
declare("MXNET_SERVE_MAX_BATCH", int, 32,
        "ServingEngine: max total rows one coalesced dispatch may carry; "
        "concurrent infer() requests batch together up to this bound",
        validator=lambda v: v >= 1, subsystem="serving", cached=False)
declare("MXNET_SERVE_MAX_DELAY_US", int, 2000,
        "ServingEngine: how long (microseconds) a dispatch may wait for "
        "more requests to coalesce before flushing the batch; 0 = "
        "dispatch immediately (no coalescing window)",
        validator=lambda v: v >= 0, subsystem="serving", cached=False)
declare("MXNET_SERVE_VERIFY", int, 1,
        "ServingEngine / hybridize(bucket=True): verify the FIRST "
        "padded/coalesced dispatch per program signature against the "
        "unpadded eager forward.  1 = default: bit-exact passes, a "
        "last-ulp kernel-rounding difference (XLA picks different gemm "
        "micro-kernels per batch extent) is accepted and counted "
        "(verify_ulp_accepts); anything larger — mean-style reductions "
        "over a padded axis — refuses bucketing explicitly.  2 = "
        "strict: bit-exact or refuse.  0 = trust padding without the "
        "check.  Trainer.compile_step(bucket=True)'s loss-value verify "
        "is ALWAYS strict (training numerics never drift).",
        validator=lambda v: v in (0, 1, 2), subsystem="serving",
        cached=False)
declare("MXNET_FORWARD_CACHE", int, 32,
        "Per-owner cap of the ProgramStore 'hybrid_forward' and "
        "'serving' namespaces: max compiled forward programs kept per "
        "HybridBlock / ServingEngine (LRU over input signatures, the "
        "inference analog of MXNET_COMPILED_STEP_CACHE); a new "
        "signature past the cap evicts the oldest.  "
        "MXNET_PROGRAM_CACHE_CAPS overrides it per namespace.",
        validator=lambda v: v > 0,
        subsystem="serving", cached=False)
declare("MXNET_KV_PAGE", int, 16,
        "Paged KV-cache (serving_decode.PagePool): tokens per cache "
        "page.  Sequences hold ceil(len/page) pages from the fixed "
        "shared HBM pool and release them at retirement; smaller pages "
        "waste less tail HBM per sequence but deepen the page-table "
        "gather inside the decode program.",
        validator=lambda v: v >= 1, subsystem="serving", cached=False)
declare("MXNET_KV_PAGES", int, 512,
        "Paged KV-cache: total pages in the process-shared pool "
        "(serving_decode.shared_pool) — the HBM budget every co-hosted "
        "GenerativeEngine draws from.  Exhaustion at admission sheds "
        "loudly (faults.ShedError, site serving.admit); exhaustion "
        "mid-decode preempts the youngest sequence (pages freed, "
        "request re-queued, greedy continuation token-exact).",
        validator=lambda v: v >= 1, subsystem="serving", cached=False)
declare("MXNET_SERVE_MAX_QUEUE", int, 64,
        "GenerativeEngine admission bound: pending generate() requests "
        "past this depth are refused immediately with faults.ShedError "
        "(site serving.admit) — overload degrades loudly, never a "
        "timeout.", validator=lambda v: v >= 1, subsystem="serving",
        cached=False)
declare("MXNET_SERVE_SLO_US", int, 0,
        "GenerativeEngine per-request latency SLO in microseconds.  "
        "0 = off.  When set, admission consults the per-bucket cost "
        "table (EMA of measured prefill/decode-step times — no trial "
        "dispatch): a request whose estimated queue wait already busts "
        "the SLO sheds at admission (ShedError, counted shed_slo); "
        "delivered requests that exceeded it count slo_violations in "
        "engine.stats().", validator=lambda v: v >= 0,
        subsystem="serving", cached=False)
declare("MXNET_SERVE_DECODE_ROWS", int, 8,
        "GenerativeEngine decode-step row capacity: the ONE compiled "
        "token-decode program always runs this many sequence rows "
        "(live sequences occupy rows, dead rows are masked), so "
        "join/retire never retraces.  Also the continuous-batching "
        "concurrency ceiling per engine.",
        validator=lambda v: v >= 1, subsystem="serving", cached=False)
declare("MXNET_PREFIX_CACHE", bool, True,
        "Content-addressed KV prefix cache (serving_decode.PagePool): "
        "pages are keyed by a rolling hash of their token block "
        "(chain-hashed, so a block's key commits to its full prefix); "
        "requests sharing a prompt reference ONE physical prefill "
        "(refcounted, copy-on-write at divergence) and prefill only "
        "the uncached suffix.  Unreferenced cached pages are kept and "
        "evicted LRU under pool pressure — PagePoolExhausted only when "
        "even eviction cannot help.  Off (0) = the pre-cache pool, "
        "byte-for-byte: no hashing, no index, prefix.* counters stay "
        "0.", subsystem="serving", cached=False)
declare("MXNET_SPEC_DECODE", bool, False,
        "Speculative decoding (serving_decode.GenerativeEngine): when "
        "on AND the engine was built with a draft model, each decode "
        "round has the cheap draft propose MXNET_SPEC_K tokens and the "
        "target score all k+1 positions in ONE bucketed verify "
        "dispatch (standard rejection sampling — the output "
        "distribution is provably the target's; exact token match "
        "under greedy).  Whether speculation PAYS is arbitrated per "
        "round from the cost table's measured draft/verify/decode "
        "EMAs, and persistently low measured acceptance auto-disables "
        "it (spec.autodisabled).  Off (0) = the plain decode loop, "
        "byte-for-byte: no draft programs, spec.* counters stay 0.",
        subsystem="serving", cached=False)
declare("MXNET_SPEC_K", str, "4",
        "Speculative decoding draft depth: tokens proposed per round "
        "(the verify program scores k+1 positions in one dispatch).  "
        "'auto' picks k per round from the cost table — measured "
        "acceptance EMA + draft/verify EMAs — over the pow2 candidate "
        "grid up to the compiled maximum.",
        validator=lambda v: v == "auto" or (v.isdigit() and int(v) >= 1),
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_PREFIX_AFFINITY", float, 1.0,
        "ReplicaRouter prefix-affinity weight: each leading page-block "
        "of a request's prompt hash chain already resident in a "
        "replica's KV pool lowers that replica's dispatch score by "
        "this much (one unit == one queued request of load), so "
        "shared-prefix traffic converges on the replica holding the "
        "warm pages.  0 disables affinity; ignored when "
        "MXNET_PREFIX_CACHE is off.",
        validator=lambda v: v >= 0, subsystem="serving", cached=False)
declare("MXNET_ROUTER_BREAKER_ERRS", int, 3,
        "ReplicaRouter circuit breaker: dispatch failures within the "
        "last MXNET_ROUTER_BREAKER_WINDOW outcomes that OPEN a "
        "replica's breaker (the replica stops receiving traffic until "
        "a half-open probe succeeds).  A wedged dispatch or replica "
        "death trips the breaker immediately, regardless of this "
        "count.", validator=lambda v: v >= 1, subsystem="serving",
        cached=False)
declare("MXNET_ROUTER_BREAKER_WINDOW", int, 16,
        "ReplicaRouter circuit breaker: size of the per-replica rolling "
        "dispatch-outcome window the error threshold "
        "(MXNET_ROUTER_BREAKER_ERRS) is evaluated over.",
        validator=lambda v: v >= 1, subsystem="serving", cached=False)
declare("MXNET_ROUTER_BREAKER_COOLDOWN_S", float, 2.0,
        "ReplicaRouter circuit breaker: seconds an OPEN breaker stays "
        "open before transitioning to HALF-OPEN, where exactly one "
        "probe request is admitted — success closes the breaker "
        "(replica re-admitted), failure re-opens it for another "
        "cooldown.  This is the probe budget the availability gate "
        "(tools/check_availability_budget.py) holds re-admission to.",
        validator=lambda v: v > 0, subsystem="serving", cached=False)
declare("MXNET_ROUTER_HEDGE_PCTL", int, 0,
        "ReplicaRouter hedged requests (the tail-at-scale move): 0 "
        "(default) = off; N in [50, 99] = a dispatch still outstanding "
        "past the fleet's p<N> dispatch latency issues ONE duplicate "
        "on a different healthy replica, first completion wins and the "
        "loser is cancelled (counted hedge_cancelled).  Hedging stays "
        "dormant until 16 latency samples exist; greedy decode keeps "
        "the duplicate token-exact, so first-wins is safe.",
        validator=lambda v: v == 0 or 50 <= v <= 99,
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_WEDGE_S", float, 30.0,
        "ReplicaRouter liveness: a dispatch outstanding this many "
        "seconds with NO heartbeat from its replica (beats are stamped "
        "per dispatch completion on the in-memory HeartbeatMonitor) "
        "declares the replica WEDGED — its breaker trips open, the "
        "dispatch is abandoned, and the request fails over to a "
        "healthy replica.  Tune well above a legitimate worst-case "
        "dispatch.", validator=lambda v: v > 0, subsystem="serving",
        cached=False)
declare("MXNET_ROUTER_EAGER_FALLBACK", bool, False,
        "ReplicaRouter last-resort degraded mode: with EVERY replica "
        "breaker open, serve single requests through the eager path "
        "(eager_generate for generative routers, the engine's unpadded "
        "eager forward for one-shot inference) instead of shedding "
        "ShedError(kind='unavailable').  Default off: shedding loudly "
        "is usually better than silently serving at eager throughput.",
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_AUTOSCALE", bool, False,
        "Elastic fleet autoscaling (FleetSupervisor.start()): a "
        "supervisor thread prices scale-up/down every "
        "MXNET_ROUTER_SCALE_INTERVAL_S from live telemetry — mean "
        "queued work per SERVING replica, worst KV page-pool "
        "pressure, fleet p99 — inside "
        "[MXNET_ROUTER_MIN_REPLICAS, MXNET_ROUTER_MAX_REPLICAS] with "
        "one action per MXNET_ROUTER_SCALE_COOLDOWN_S.  Scale-down "
        "is a scheduled graceful preemption: drain_replica (typed "
        "draining handback, clean page audit) then SIGTERM -> exit "
        "MXNET_PREEMPTION_EXIT_CODE for process-backed replicas.  "
        "Default off: FleetSupervisor.start() is a no-op — no "
        "thread, no timer, dispatch identical to the static router.",
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_MIN_REPLICAS", int, 1,
        "Elastic fleet floor: the autoscaler never drains below this "
        "many SERVING replicas, and scales UP toward it regardless of "
        "load/cooldown when the fleet falls under (self-healing after "
        "a host loss).", validator=lambda v: v >= 1,
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_MAX_REPLICAS", int, 4,
        "Elastic fleet ceiling: the autoscaler never joins past this "
        "many SERVING replicas, however saturated the fleet signals "
        "are.", validator=lambda v: v >= 1, subsystem="serving",
        cached=False)
declare("MXNET_ROUTER_SCALE_COOLDOWN_S", float, 10.0,
        "Autoscaler stability: at most one scaling action (up or "
        "down) per this many seconds, so a bursty load cannot flap "
        "the fleet — except scaling up toward MXNET_ROUTER_"
        "MIN_REPLICAS, which is urgent and bypasses the cooldown.",
        validator=lambda v: v >= 0, subsystem="serving", cached=False)
declare("MXNET_ROUTER_SCALE_INTERVAL_S", float, 1.0,
        "Autoscaler cadence: seconds between supervisor ticks (each "
        "tick reads the fleet signals and executes at most one "
        "scaling action).", validator=lambda v: v > 0,
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_SCALE_UP_QUEUE", float, 1.5,
        "Autoscaler scale-up threshold: mean queued work per SERVING "
        "replica (engine load(): queue_depth + in_flight occupancy) "
        "at or above which a tick prices a scale-up.  Measured from "
        "the same load() surface the router balances on — never a "
        "static request count.", validator=lambda v: v > 0,
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_SCALE_DOWN_QUEUE", float, 0.1,
        "Autoscaler scale-down threshold: mean queued work per "
        "SERVING replica at or below which (with page-pool pressure "
        "also low) a tick prices a scale-down, never below "
        "MXNET_ROUTER_MIN_REPLICAS.", validator=lambda v: v >= 0,
        subsystem="serving", cached=False)
declare("MXNET_ROUTER_SCALE_POOL_HIGH", float, 0.85,
        "Autoscaler KV-pressure threshold: worst per-replica page-"
        "pool pressure (1 - free/total) at or above which a tick "
        "prices a scale-up even with short queues — pool exhaustion "
        "sheds, so headroom is capacity.  Scale-down additionally "
        "requires pressure under half this value.",
        validator=lambda v: 0 < v <= 1, subsystem="serving",
        cached=False)
declare("MXNET_ROUTER_REMOTE_TIMEOUT_S", float, 120.0,
        "RemoteReplica transport ceiling: seconds a framed call may "
        "wait on connect/reply before the client raises a "
        "TransientFault (breaker-blamed, request fails over).  The "
        "ambient request deadline tightens this per-call; the ceiling "
        "bounds deadline-less dispatches so a dead host can never "
        "hang a router worker thread forever.",
        validator=lambda v: v > 0, subsystem="serving", cached=False)
declare("MXNET_TELEMETRY_DIR", str, None,
        "Telemetry flight recorder: when set, telemetry.flush() — called "
        "by engine.waitall() and available directly — appends the "
        "structured event bus plus a full counter snapshot as JSON-lines "
        "to <dir>/telemetry-<pid>.jsonl.  Unset (default) = recorder "
        "off; counters/events/spans stay purely in-process.",
        subsystem="telemetry", cached=False)
declare("MXNET_TELEMETRY_EVENTS", int, 4096,
        "Telemetry event-bus capacity: the bounded buffer keeps the "
        "newest N structured events (retrace, fallback, shed, preempt, "
        "cache_evict, amp_overflow, fault.*); older events drop (the "
        "emitted counter telemetry.events keeps the true total).  Read "
        "once at import.", validator=lambda v: v >= 1,
        subsystem="telemetry")
declare("MXNET_TELEMETRY_TRACE", int, 1,
        "End-to-end request tracing: every request admitted by the "
        "serving entry points (ReplicaRouter.infer/generate, bare "
        "ServingEngine.infer, GenerativeEngine.generate) mints a "
        "trace_id carried in a thread-local trace context that the "
        "router's dispatch/hedge threads and the decode scheduler "
        "re-enter — shed/failover/hedge/breaker/fault events and "
        "serving/decode spans all stamp it, telemetry.trace(id) "
        "returns the stitched lifecycle, and the chrome export links "
        "one request as one flow.  0 = no ids minted, no trace fields "
        "anywhere, zero overhead (the dispatch/retrace budget is "
        "byte-identical).", subsystem="telemetry", cached=False)
declare("MXNET_TELEMETRY_MAX_MB", float, 64.0,
        "Flight-recorder size cap: when the MXNET_TELEMETRY_DIR shard "
        "directory exceeds this many megabytes after a flush, the "
        "oldest-mtime shards (never the flushing process's own) are "
        "deleted until it fits (counted in telemetry.shards_rotated). "
        "<= 0 disables rotation.", subsystem="telemetry", cached=False)
declare("MXNET_TELEMETRY_XLA", int, 1,
        "Wrap telemetry.span brackets in jax.profiler trace annotations "
        "so host-side spans (train step, serving dispatch, decode "
        "iteration) land INSIDE XLA device profiles captured via "
        "jax.profiler/TensorBoard.  0 = spans record host-side only.",
        subsystem="telemetry", cached=False)
declare("MXNET_FAULT_EVENTS", int, 1024,
        "Capacity of the faults structured event log (faults.events()): "
        "the bounded deque keeps the newest N entries (retry, raise, "
        "deadline, inject, degradation records).  Read once at import; "
        "fault events also mirror onto the telemetry bus with step "
        "indices.", validator=lambda v: v >= 1, subsystem="faults")
declare("DMLC_ROLE", str, None,
        "Process role for launcher-spawned jobs (reference ps-lite "
        "DMLC_ROLE): 'worker' (default when unset), 'server', or "
        "'scheduler'.  On TPU server/scheduler roles only park "
        "(collectives replace parameter servers); see "
        "kvstore/kvstore_server.py.", subsystem="kvstore", cached=False)
declare("MXNET_ROLE", str, None,
        "Fallback alias for DMLC_ROLE (checked second by "
        "kvstore_server.role())", subsystem="kvstore", cached=False)
declare("MXNET_TPU_COORDINATOR", str, None,
        "host:port of process 0 for jax.distributed bootstrap (set by "
        "tools/launch.py; unset = single-process)", subsystem="kvstore",
        cached=False)
declare("MXNET_TPU_NUM_PROCS", int, None,
        "Multi-controller world size for jax.distributed bootstrap "
        "(set by tools/launch.py alongside MXNET_TPU_COORDINATOR)",
        subsystem="kvstore", cached=False)
declare("MXNET_TPU_PROC_ID", int, None,
        "This process' rank for jax.distributed bootstrap (set by "
        "tools/launch.py alongside MXNET_TPU_COORDINATOR)",
        subsystem="kvstore", cached=False)
declare("MXNET_TPU_STOP_FILE", str, None,
        "Path whose existence stops a parked 'server'/'scheduler' role "
        "process (KVStoreServer.run poll loop)", subsystem="kvstore",
        cached=False)
declare("MXNET_LIBRARY_PATH", str, None,
        "Override path to the native runtime library "
        "(libinfo.find_lib_path; reference MXNET_LIBRARY_PATH)",
        subsystem="io", cached=False)
declare("MXNET_TEST_DEVICE", str, None,
        "Device the test suite's default_context() targets, as "
        "'kind[:index]' (e.g. 'gpu:0'); unset = the process default "
        "context (reference test harness contract)",
        subsystem="testing", cached=False)
declare("MXNET_LINT_RUNTIME", int, 0,
        "graftlint runtime concurrency layer (tools/lint/runtime.py): "
        "1 = instrument threading.Lock/RLock acquisition and record "
        "the cross-thread lock-order graph for the deadlock gate "
        "(`python -m tools.lint --runtime`).  Read RAW pre-import by "
        "the lint harness — instrumentation must install before "
        "mxnet_tpu's module-level locks are created — and declared "
        "here so this table documents it.  0 = off (default): "
        "production processes pay zero overhead.",
        validator=lambda v: v in (0, 1), subsystem="testing",
        cached=False)
declare("MXNET_MODULE_SEED", int, None,
        "Override the per-test RNG seed for reproduction (reference test "
        "harness contract)", subsystem="testing")
declare("MXNET_TEST_SEED", int, None,
        "Per-test seed printed by the conftest on failure",
        subsystem="testing")
declare("MXNET_SAFE_ACCUMULATION", bool, True,
        "Accumulate fp16/bf16 reductions in fp32 (reference "
        "MXNET_SAFE_ACCUMULATION; XLA does this for MXU matmuls by "
        "default)", subsystem="ops")
declare("MXNET_GPU_MEM_POOL_TYPE", str, "Round",
        "Accepted for parity; PJRT owns HBM pooling on TPU",
        subsystem="memory")
declare("MXNET_PROFILER_AUTOSTART", bool, False,
        "Start the profiler at import (reference profiler env var)",
        subsystem="profiler")
declare("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
        "Accepted for parity; XLA whole-graph compilation subsumes "
        "engine op bulking", subsystem="engine")
declare("GRAFT_NDEV", int, 8,
        "__graft_entry__ dryrun virtual device count", subsystem="testing")
