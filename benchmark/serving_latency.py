"""Serving-path latency: the shape-bucketed compiled inference engine
(``mxnet_tpu/serving.py``) driven by a randomized variable-length request
stream, plus the GENERATIVE lanes over ``serving_decode``.

Reports per-request p50/p99 latency, throughput, bucket hits/misses,
compiled-program count, and the retrace count after warm-up — the PR-4
acceptance bar is **0 steady-state retraces with the program count
bounded by the bucket grid** (counter-based, so the lane is meaningful on
any backend; the latency numbers are device numbers only on a chip
run).  A second phase fires the same stream from concurrent threads to
exercise the micro-batcher (coalesced requests per dispatch).

``--serve-only --json`` emits just the lane dict (bench.py's ``infer``
lanes[] entry).  Like benchmark/eager_latency.py, the measured work runs
in a SUBPROCESS so jit caches and config are clean.

``--decode-only --json`` is bench.py's ``decode`` lane: the
continuous-batching A/B — the SAME request set generated
one-request-at-a-time (sequential submission, no row sharing) vs at
concurrency >= 8 through the iteration-level scheduler — whose
acceptance bar is **>= 2x tokens/s from continuous batching** with 0
retraces, plus a compact multi-tenant STORM: bursty Poisson arrivals
of mixed-length prompts against a fast model co-hosted with a
deliberately slow model on the SHARED KV page pool, reporting
per-model p50/p99, shed count, tokens/s, and the interference ratio
(fast model storm-p99 / solo-p99 — bounded misbehavior, not silent
collapse), and a ROUTER storm (ISSUE 14): two fast replicas behind a
``serving_router.ReplicaRouter`` with one replica killed mid-storm,
stamping the availability columns — dropped (must be 0) / hedged /
failed_over / breaker_transitions — next to the latency numbers, and
an ELASTIC storm (ISSUE 17): one replica plus a ``FleetSupervisor``
under the same bursty arrivals, stamping the replica-count timeline,
scale_ups/scale_downs/joins/drains, peak/final replica counts, and
fleet tokens/s. ``--storm`` prints the storm report standalone.

``--shared-prefix`` is the ISSUE-16 lane: M users x ONE system prompt
through the content-addressed prefix cache (``MXNET_PREFIX_CACHE``),
run warm (cache on) and cold (knob off) over the same seeds, stamping
``prefix_hit_rate`` (acceptance floor >= 0.9), prefill tokens/FLOPs
saved, tokens/s/chip for both passes, and token-exactness vs the cold
pass AND the eager oracle.  ``prefix_miss_blocks`` rides the lane dict
so tools/check_perf_delta.py gates hit-rate regressions round over
round.

``--speculative`` is the ISSUE-19 lane: the SAME greedy prompt set
through a high-agreement draft/target pair with ``MXNET_SPEC_DECODE=1``
vs the non-spec baseline, stamping tokens/s, measured acceptance,
tokens-per-round, and target-dispatches-per-token — the worker ENFORCES
the acceptance bars (>= 1.5x tokens/s at acceptance >= 0.7, token-exact
vs the eager oracle, low-agreement draft auto-disabled with tokens/s
never regressing past 5% of baseline).

Usage: python benchmark/serving_latency.py [--json] [--serve-only]
           [--decode-only] [--storm] [--shared-prefix] [--speculative]
           [--requests N] [--threads T]
"""
import json
import os
import subprocess
import sys

_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import gluon, serving
from mxnet_tpu.gluon import nn

N_REQ = int(os.environ.get("SERVE_REQUESTS", "64"))
THREADS = int(os.environ.get("SERVE_THREADS", "4"))
WIDTH = int(os.environ.get("SERVE_WIDTH", "64"))
MAXLEN = int(os.environ.get("SERVE_MAXLEN", "32"))

class Net(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.d1 = nn.Dense(WIDTH, in_units=WIDTH, activation="relu")
        self.d2 = nn.Dense(WIDTH, in_units=WIDTH, activation="relu")
        self.out = nn.Dense(8, in_units=WIDTH)
    def forward(self, x):
        return self.out(self.d2(self.d1(x)))

net = Net()
net.initialize(mx.init.Xavier())
rng = onp.random.RandomState(0)
lengths = rng.randint(1, MAXLEN + 1, size=N_REQ).tolist()
reqs = [mx.nd.array(rng.randn(n, WIDTH).astype(onp.float32))
        for n in lengths]

eng = serving.ServingEngine(net, max_delay_us=200)
# deploy-time AOT warmup (ProgramStore): compile the pow2 grid up to
# MAXLEN off the request path; compile_s is the whole tax paid here
from mxnet_tpu import program_store
t_warm = time.perf_counter()
warmup_programs = eng.warmup(
    mx.nd.array(onp.zeros((1, WIDTH), onp.float32)), max_rows=MAXLEN)
compile_s = time.perf_counter() - t_warm
# the first real request per bucket still pays its one-time verify
b = 1
while b <= MAXLEN:
    eng.infer(mx.nd.array(rng.randn(b, WIDTH).astype(onp.float32)))
    b <<= 1
warm_traces = serving.trace_count()
warm_progs = len(eng._programs)

# phase 1: sequential stream (per-request latency, retrace bar)
t0 = serving.trace_count(); d0 = serving.dispatch_count()
h0 = serving.bucket_stats()
t_start = time.perf_counter()
outs = [eng.infer(r) for r in reqs]
_ = float(outs[-1].asnumpy().ravel()[0])          # fence
dt = time.perf_counter() - t_start
seq = eng.stats()
retraces = serving.trace_count() - t0
h1 = serving.bucket_stats()

# phase 2: concurrent stream (micro-batcher coalescing)
import threading
eng2 = serving.ServingEngine(net, max_delay_us=3000)
for bb in (1, 2, 4, 8, 16, 32, 64):
    if bb <= serving.BucketPolicy().bucket(MAXLEN * THREADS):
        eng2.infer(mx.nd.array(rng.randn(bb, WIDTH).astype(onp.float32)))
errs = []
def fire(chunk):
    try:
        for r in chunk:
            eng2.infer(r)
    except BaseException as e:
        errs.append(repr(e))
threads = [threading.Thread(target=fire, args=(reqs[i::THREADS],))
           for i in range(THREADS)]
t2 = time.perf_counter()
for t in threads: t.start()
for t in threads: t.join()
dt2 = time.perf_counter() - t2
conc = eng2.stats()
assert not errs, errs

import jax
from mxnet_tpu import telemetry
telemetry.flush()   # flight-recorder shard for the lane's fleet merge
_disk = program_store.disk_stats()
print(json.dumps({
    "platform": jax.default_backend(),
    # full namespaced counter snapshot (process-fresh == delta from 0);
    # the hand-picked keys below stay as aliases for BENCH_* continuity
    "telemetry": {k: v for k, v in telemetry.snapshot().items() if v},
    "requests": N_REQ,
    "buckets": serving.BucketPolicy().spec,
    "programs": seq["programs"],
    "warmup_programs": warmup_programs,
    "compile_s": round(compile_s, 3),
    "cache_hits": _disk["hits"],
    "cache_misses": _disk["misses"],
    "warm_traces": warm_traces,
    "retraces_after_warm": retraces,
    "bucket_hits": h1["hits"] - h0["hits"],
    "bucket_misses": h1["misses"] - h0["misses"],
    "dispatches": serving.dispatch_count() - d0,
    "p50_us": seq["p50_us"],
    "p99_us": seq["p99_us"],
    "throughput_rps": N_REQ / dt,
    "concurrent": {
        "threads": THREADS,
        "batches": conc["batches"],
        "requests": conc["requests"],
        "coalesced": conc["coalesced"],
        "requests_per_dispatch": conc["requests"] / max(conc["batches"], 1),
        "p99_us": conc["p99_us"],
        "throughput_rps": conc["requests"] / dt2,
    },
}))
eng.close(); eng2.close()
"""


_DECODE_WORKER = r"""
import json, os, sys, threading, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
from mxnet_tpu import program_store, serving_decode as sd

CONC = int(os.environ.get("DECODE_CONCURRENCY", "8"))
REQS = int(os.environ.get("DECODE_REQUESTS", "16"))
NEW = int(os.environ.get("DECODE_NEW_TOKENS", "8"))
STORM = os.environ.get("DECODE_STORM", "1") == "1"

def fast_model():
    return sd.TinyCausalLM(vocab=128, d_model=64, n_layers=2, n_heads=4,
                           max_seq=64)

def slow_model():
    # the deliberately slow co-tenant: ~2.5x the per-step FLOPs of the
    # fast model — slow per TOKEN, while its own admission queue
    # (max_queue below) bounds how much of the host it can occupy.
    # Interference is bounded by the WORST single slow dispatch (the
    # gate is non-preemptive), so the tenant is deep, not wide.
    return sd.TinyCausalLM(vocab=128, d_model=72, n_layers=4, n_heads=4,
                           max_seq=64)

rng = onp.random.RandomState(0)
def mk_prompts(n, lo=2, hi=17):
    return [rng.randint(0, 128, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]

def drive(eng, prompts, conc, poisson_rate=None, new=NEW):
    '''Submit prompts from conc client threads (optionally with bursty
    Poisson inter-arrival sleeps); returns (wall_s, tokens, sheds).'''
    errs, sheds, tokens = [], [0], [0]
    lock = threading.Lock()
    def fire(chunk):
        for p in chunk:
            if poisson_rate:
                time.sleep(rng.exponential(1.0 / poisson_rate))
            try:
                out = eng.generate(p, max_new_tokens=new)
                with lock:
                    tokens[0] += len(out)
            except sd.ShedError:
                with lock:
                    sheds[0] += 1
            except BaseException as e:
                errs.append(e)
    threads = [threading.Thread(target=fire, args=(prompts[i::conc],))
               for i in range(conc)]
    t0 = time.perf_counter()
    for t in threads: t.start()
    for t in threads: t.join()
    if errs:
        raise errs[0]
    return time.perf_counter() - t0, tokens[0], sheds[0]

# ---- continuous-batching A/B ------------------------------------------
model = fast_model(); params = model.init_params(0)
pool = sd.PagePool(pages=256, page=8)
eng = sd.GenerativeEngine(model, params=params, pool=pool,
                          max_rows=max(8, CONC), name="fast")
t_warm = time.perf_counter()
warmup_programs = eng.warmup(max_len=16)
compile_s = time.perf_counter() - t_warm
prompts = mk_prompts(REQS)
eng.generate(prompts[0], max_new_tokens=2)       # first-dispatch warm
t0, d0 = sd.trace_count(), sd.dispatch_count()
seq_s, seq_tok, _ = drive(eng, prompts, conc=1)  # one request at a time
conc_s, conc_tok, _ = drive(eng, prompts, conc=CONC)
st = eng.stats()
retraces = sd.trace_count() - t0
seq_tps, conc_tps = seq_tok / seq_s, conc_tok / conc_s

out = {
    "platform": __import__("jax").default_backend(),
    "requests": REQS, "concurrency": CONC, "new_tokens": NEW,
    "programs": st["programs"], "warmup_programs": warmup_programs,
    "compile_s": round(compile_s, 3),
    "retraces_after_warm": retraces,
    "dispatches": sd.dispatch_count() - d0,
    "rows_per_decode": round(st.get("rows_per_decode", 0.0), 2),
    "sequential_tokens_s": round(seq_tps, 1),
    "continuous_tokens_s": round(conc_tps, 1),
    "batching_speedup": round(conc_tps / max(seq_tps, 1e-9), 2),
    "p50_us": round(st["p50_us"], 1), "p99_us": round(st["p99_us"], 1),
    "pool": {k: st["pool"][k] for k in
             ("pages", "page", "in_use", "high_water")},
}
eng.close()

# ---- multi-tenant storm ------------------------------------------------
if STORM:
    fparams, sparams = params, slow_model().init_params(1)
    def storm_phase(with_slow):
        pool = sd.PagePool(pages=256, page=8)
        # the fast tenant carries an SLO -> it outranks the slow tenant
        # at the shared dispatch gate (most-urgent-first ordering)
        fe = sd.GenerativeEngine(fast_model(), params=fparams, pool=pool,
                                 max_rows=8, name="fast",
                                 slo_us=500_000)
        fe.warmup(max_len=16)
        agents = []
        if with_slow:
            se = sd.GenerativeEngine(slow_model(), params=sparams,
                                     pool=pool, max_rows=2, max_queue=2,
                                     name="slow")
            se.warmup(max_len=16)        # covers the 4..12-token prompts
            # the slow tenant gets hammered past its tiny queue so the
            # storm also shows load SHEDDING, not just interference —
            # shed requests are refused at ADMISSION (no compute), so
            # arrival pressure exceeds its 2-row/2-queue capacity
            # without the host saturating (which would measure CPU
            # contention, not co-tenancy)
            agents.append((se, mk_prompts(14, 4, 13), 7, 50.0))
        # >= 101 fast samples so p99 is a real percentile, not the
        # single unluckiest burst
        agents.append((fe, mk_prompts(104), 8, 40.0))
        results = {}
        def run(eng, prompts, conc, rate):
            results[eng.name] = drive(eng, prompts, conc,
                                      poisson_rate=rate)
        ths = [threading.Thread(target=run, args=a) for a in agents]
        for t in ths: t.start()
        for t in ths: t.join()
        stats = {}
        for eng, _p, _c, _r in agents:
            s = eng.stats()
            wall, tok, shed = results[eng.name]
            stats[eng.name] = {
                "p50_us": round(s["p50_us"], 1),
                "p99_us": round(s["p99_us"], 1),
                "tokens_s": round(tok / wall, 1),
                "shed": s["shed"], "preempts": s["preempts"],
                "slo_violations": s["slo_violations"],
                "delivered": s["delivered"],
            }
            eng.close()
        return stats
    solo = storm_phase(with_slow=False)["fast"]
    storm = storm_phase(with_slow=True)
    out["storm"] = {
        "fast_solo_p99_us": solo["p99_us"],
        "fast": storm["fast"], "slow": storm["slow"],
        "interference_p99_ratio": round(
            storm["fast"]["p99_us"] / max(solo["p99_us"], 1e-9), 2),
        "shed_total": storm["fast"]["shed"] + storm["slow"]["shed"],
    }

    # ---- router storm: the availability columns -----------------------
    # 2 replicas behind a ReplicaRouter, bursty arrivals, one replica
    # KILLED mid-storm: the columns the fault-tolerant serving plane is
    # judged on — dropped (must be 0), hedged, failed_over, breaker
    # transitions — ride the bench artifact so availability regressions
    # are visible round over round like every perf number.
    from mxnet_tpu.serving_router import ReplicaRouter
    rpools = [sd.PagePool(pages=256, page=8) for _ in range(2)]
    rengines = [sd.GenerativeEngine(fast_model(), params=fparams,
                                    pool=rpools[i], max_rows=8,
                                    name=f"rr{i}") for i in range(2)]
    for e in rengines:
        e.warmup(max_len=16)
    router = ReplicaRouter(rengines, name="bench", breaker_errs=2,
                           breaker_cooldown_s=0.5, hedge_pctl=95)
    rprompts = mk_prompts(48)
    delivered, shed, rerrs = [0], [0], []
    rlock = threading.Lock()
    def rfire(chunk):
        for p in chunk:
            time.sleep(rng.exponential(1.0 / 40.0))
            try:
                router.generate(p, max_new_tokens=NEW,
                                deadline_us=20_000_000)
                with rlock:
                    delivered[0] += 1
            except sd.ShedError:
                with rlock:
                    shed[0] += 1
            except BaseException as e:
                rerrs.append(repr(e))
    rthreads = [threading.Thread(target=rfire, args=(rprompts[i::8],))
                for i in range(8)]
    t0 = time.perf_counter()
    for t in rthreads: t.start()
    time.sleep(0.3)                       # storm rolling: kill replica 0
    def rboom(*a, **k):
        raise RuntimeError("bench replica kill")
    rengines[0].generate = rboom
    for t in rthreads: t.join()
    rwall = time.perf_counter() - t0
    rst = router.stats()
    out["router_storm"] = {
        "requests": len(rprompts),
        "delivered": delivered[0],
        "dropped": len(rprompts) - delivered[0] - shed[0],
        "shed": shed[0],
        "errors": rerrs,
        "hedged": rst["hedges"],
        "failed_over": rst["failovers"],
        "breaker_transitions": (rst["breaker_opens"]
                                + rst["breaker_half_opens"]
                                + rst["breaker_closes"]),
        "p50_us": round(rst["p50_us"], 1),
        "p99_us": round(rst["p99_us"], 1),
        "tokens_s": round(delivered[0] * NEW / rwall, 1),
        "wall_s": round(rwall, 2),
    }
    for e in rengines:
        e.close()

    # ---- elastic storm: the ISSUE-17 autoscaler columns ---------------
    # 1 replica + a FleetSupervisor under the same bursty arrivals: the
    # artifact stamps the replica-count TIMELINE, the scale event
    # counts, and fleet tokens/s — autoscaler regressions (flapping,
    # never scaling, slow joins, failure to shrink back) show up round
    # over round like every latency number.
    from mxnet_tpu.serving_router import FleetSupervisor
    def espawn():
        epool = sd.PagePool(pages=256, page=8)
        ee = sd.GenerativeEngine(fast_model(), params=fparams,
                                 pool=epool, max_rows=8,
                                 name="elastic")
        ee.warmup(max_len=16)
        return ee
    erouter = ReplicaRouter([espawn()], name="elastic",
                            breaker_errs=2, breaker_cooldown_s=0.5,
                            hedge_pctl=95)
    def eretire(eng_, index):
        eng_.close()
    esup = FleetSupervisor(erouter, espawn, retire=eretire,
                           enabled=True, min_replicas=1,
                           max_replicas=3, cooldown_s=0.4,
                           interval_s=0.05, up_queue=1.0,
                           down_queue=0.1,
                           warmup_kwargs={"max_len": 16})
    esup.start()
    # long enough a burst that the first join COMPLETES mid-storm (an
    # in-process spawn pays a warmup, not a process boot)
    eprompts = mk_prompts(288)
    edelivered, eshed, eerrs = [0], [0], []
    elock = threading.Lock()
    def efire(chunk):
        for p in chunk:
            time.sleep(rng.exponential(1.0 / 60.0))
            try:
                erouter.generate(p, max_new_tokens=NEW,
                                 deadline_us=30_000_000)
                with elock:
                    edelivered[0] += 1
            except sd.ShedError:
                with elock:
                    eshed[0] += 1
            except BaseException as e:
                eerrs.append(repr(e))
    ethreads = [threading.Thread(target=efire,
                                 args=(eprompts[i::12],))
                for i in range(12)]
    timeline = []
    t0 = time.perf_counter()
    for t in ethreads: t.start()
    while any(t.is_alive() for t in ethreads):
        timeline.append([round(time.perf_counter() - t0, 2),
                         erouter.serving_replicas()])
        time.sleep(0.05)
    for t in ethreads: t.join()
    ewall = time.perf_counter() - t0
    # let the burst subside so the supervisor shrinks back to the
    # floor; the minimum wait catches a join that completes just after
    # the last request (a spawn in flight when the storm ended)
    tdown_min = time.perf_counter() + 3.0
    tdown_max = time.perf_counter() + 20.0
    while time.perf_counter() < tdown_max and (
            time.perf_counter() < tdown_min
            or erouter.serving_replicas() > 1):
        timeline.append([round(time.perf_counter() - t0, 2),
                         erouter.serving_replicas()])
        time.sleep(0.05)
    esup.stop()
    efleet = erouter.fleet_stats()
    out["elastic_storm"] = {
        "requests": len(eprompts),
        "delivered": edelivered[0],
        "dropped": len(eprompts) - edelivered[0] - eshed[0],
        "shed": eshed[0],
        "errors": eerrs,
        "scale_ups": efleet["scale_ups"],
        "scale_downs": efleet["scale_downs"],
        "joins": efleet["joins"],
        "drains": efleet["drains"],
        "scale_errors": efleet["scale_errors"],
        "peak_replicas": max((n for _, n in timeline), default=1),
        "final_replicas": erouter.serving_replicas(),
        "replica_timeline": timeline[:400],
        "fleet_tokens_s": round(edelivered[0] * NEW / ewall, 1),
        "wall_s": round(ewall, 2),
    }
    for r in list(erouter._replicas):
        if hasattr(r.engine, "close"):
            r.engine.close()

_disk = program_store.disk_stats()
out["cache_hits"] = _disk["hits"]
out["cache_misses"] = _disk["misses"]
from mxnet_tpu import telemetry
telemetry.flush()   # flight-recorder shard for the lane's fleet merge
# full namespaced counter snapshot (process-fresh == delta from 0);
# the hand-picked keys above stay as aliases for BENCH_* continuity
out["telemetry"] = {k: v for k, v in telemetry.snapshot().items() if v}
print(json.dumps(out))
"""


_PREFIX_WORKER = r"""
import json, os, sys, threading, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
import jax
from mxnet_tpu import serving_decode as sd, telemetry

USERS = int(os.environ.get("PREFIX_USERS", "16"))
NEW = int(os.environ.get("PREFIX_NEW_TOKENS", "8"))

def fast_model():
    return sd.TinyCausalLM(vocab=128, d_model=64, n_layers=2, n_heads=4,
                           max_seq=64)

model = fast_model(); params = model.init_params(0)
rng = onp.random.RandomState(0)
# the one shared system prompt: 32 tokens = 4 full page-8 blocks, so
# USERS identical prompts prefill once and the rest full-hit.  Hit rate
# over the storm = (USERS-1)*4 / (USERS*4) = 0.9375 for USERS=16 — the
# >= 0.9 acceptance floor with margin, and deterministic.
SYS = rng.randint(0, 128, size=32).tolist()

def storm(knob):
    '''One pass of the USERS-identical-prompt storm with the prefix
    cache forced on/off; returns (outputs, wall_s, prefix counter
    deltas, prefill dispatch count).'''
    os.environ["MXNET_PREFIX_CACHE"] = knob
    pool = sd.PagePool(pages=256, page=8)
    eng = sd.GenerativeEngine(fast_model(), params=params, pool=pool,
                              max_rows=max(8, USERS), name="px" + knob)
    eng.warmup(max_len=16)
    eng.generate(rng.randint(0, 128, size=5).tolist(), max_new_tokens=2)
    base = telemetry.snapshot()
    outs = {}
    errs = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    # primer: the one physical prefill the shared prompt should cost
    outs[0] = eng.generate(list(SYS), max_new_tokens=NEW)
    def fire(uid):
        try:
            out = eng.generate(list(SYS), max_new_tokens=NEW)
            with lock:
                outs[uid] = out
        except BaseException as e:
            errs.append(repr(e))
    ths = [threading.Thread(target=fire, args=(u,))
           for u in range(1, USERS)]
    for t in ths: t.start()
    for t in ths: t.join()
    wall = time.perf_counter() - t0
    if errs:
        raise RuntimeError("; ".join(errs))
    delta = telemetry.delta(base)
    prefills = sum(int(v) for k, v in delta.items()
                   if k.startswith("decode.engine")
                   and k.endswith(".prefills"))
    eng.close()
    if pool.in_use():
        raise RuntimeError(f"leaked {pool.in_use()} pages (knob={knob})")
    bad = pool.audit()
    if bad:
        raise RuntimeError(f"pool audit failed (knob={knob}): {bad}")
    px = {k.split(".", 1)[1]: int(v) for k, v in delta.items()
          if k.startswith("prefix.")}
    return [outs[u] for u in range(USERS)], wall, px, delta, prefills

warm_outs, warm_wall, px, warm_delta, warm_prefills = storm("1")
cold_outs, cold_wall, px_off, _cold_delta, cold_prefills = storm("0")
if any(v for v in px_off.values()):
    raise RuntimeError(f"prefix counters nonzero with the knob off: {px_off}")
oracle = list(sd.eager_generate(model, params, list(SYS),
                                max_new_tokens=NEW))
token_exact = all(o == oracle for o in warm_outs) and \
    all(o == oracle for o in cold_outs)

hit = px.get("hit_blocks", 0)
miss = px.get("miss_blocks", 0)
hit_rate = hit / max(hit + miss, 1)
page = 8
# prefill work avoided: every hit block skips `page` prompt tokens of
# prefill compute.  FLOPs estimated analytically from the model dims
# (projections + MLP; attention's quadratic term excluded, so the
# stamp is a floor).
d = model.d_model
flops_per_tok = model.n_layers * (8 * d * d + 4 * d * d)
tokens_saved = hit * page
chips = max(jax.device_count(), 1)
lane = {
    "metric": "prefix_shared_storm",
    "platform": jax.default_backend(),
    "users": USERS, "prompt_tokens": len(SYS), "new_tokens": NEW,
    "prefix_hit_rate": round(hit_rate, 4),
    "prefix_hit_blocks": hit, "prefix_miss_blocks": miss,
    "prefix_cow_forks": px.get("cow_forks", 0),
    "prefix_evictions": px.get("evictions", 0),
    "prefill_tokens_saved": tokens_saved,
    "prefill_flops_saved": tokens_saved * flops_per_tok,
    "prefills_warm": warm_prefills, "prefills_cold": cold_prefills,
    "warm_wall_s": round(warm_wall, 3), "cold_wall_s": round(cold_wall, 3),
    "warm_tokens_s_per_chip": round(USERS * NEW / warm_wall / chips, 1),
    "cold_tokens_s_per_chip": round(USERS * NEW / cold_wall / chips, 1),
    "token_exact": token_exact,
}
telemetry.flush()   # flight-recorder shard for the lane's fleet merge
lane["telemetry"] = {k: v for k, v in warm_delta.items() if v}
print(json.dumps(lane))
"""


_SPEC_WORKER = r"""
import json, os, sys, threading, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
import jax
from mxnet_tpu import serving_decode as sd, telemetry

REQS = int(os.environ.get("SPEC_REQUESTS", "12"))
NEW = int(os.environ.get("SPEC_NEW_TOKENS", "24"))
K = int(os.environ.get("SPEC_K", "4"))
ENFORCE = os.environ.get("SPEC_ENFORCE", "1") == "1"

# the high-agreement pair: a deep target whose extra layers are
# identity, so draft logits == target logits (acceptance 1.0 by
# construction) while the target still pays 8x the draft's per-token
# compute — the workload speculation exists for
target, tp, draft, dp = sd.high_agreement_pair(
    vocab=128, d_model=64, target_layers=8, draft_layers=1,
    n_heads=4, max_seq=96, seed=0)

rng = onp.random.RandomState(0)
prompts = [rng.randint(0, 128, size=rng.randint(4, 13)).tolist()
           for _ in range(REQS)]

def run(spec_on, draft_model=None, draft_params=None, label="x"):
    '''One pass of the SAME greedy prompt set; returns tokens/s and the
    spec counters.  The knob is uncached, so the env flip scopes to
    the engine built under it.'''
    os.environ["MXNET_SPEC_DECODE"] = "1" if spec_on else "0"
    pool = sd.PagePool(pages=256, page=8)
    kw = (dict(draft=draft_model, draft_params=draft_params, spec_k=K)
          if draft_model is not None else {})
    # max_rows=2: decode-bound rows, the workload the k-for-1 verify
    # win targets (wide batches amortize dispatch on their own)
    eng = sd.GenerativeEngine(target, params=tp, pool=pool, max_rows=2,
                              name="spec_" + label, **kw)
    eng.warmup(max_len=16)
    eng.generate(prompts[0], max_new_tokens=2)   # first-dispatch warm
    outs, errs = {}, []
    lock = threading.Lock()
    def fire(i):
        try:
            out = eng.generate(prompts[i], max_new_tokens=NEW)
            with lock:
                outs[i] = out
        except BaseException as e:
            errs.append(repr(e))
    ths = [threading.Thread(target=fire, args=(i,)) for i in range(REQS)]
    t0 = time.perf_counter()
    for t in ths: t.start()
    for t in ths: t.join()
    wall = time.perf_counter() - t0
    if errs:
        raise RuntimeError("; ".join(errs))
    st = eng.stats()
    eng.close()
    if pool.in_use():
        raise RuntimeError(f"leaked {pool.in_use()} pages ({label})")
    bad = pool.audit()
    if bad:
        raise RuntimeError(f"pool audit failed ({label}): {bad}")
    toks = sum(len(o) for o in outs.values())
    return {
        "outs": [outs[i] for i in range(REQS)],
        "wall_s": wall, "tokens": toks, "tokens_s": toks / wall,
        "rounds": st["spec_rounds"], "proposed": st["spec_proposed"],
        "accepted": st["spec_accepted"],
        "fallbacks": st["spec_fallbacks"],
        "disabled": st["spec_disabled"],
        "decode_steps": st["decode_steps"],
    }

base = run(False, label="base")          # the non-spec baseline
# LOW-agreement leg first (so the final spec.* gauge snapshot reflects
# the healthy high-agreement pass): an independently-initialized draft
# whose proposals rarely match — the cost table must auto-disable and
# tokens/s must stay within 5% of baseline (never a regression)
low_draft = sd.TinyCausalLM(vocab=128, d_model=64, n_layers=1,
                            n_heads=4, max_seq=96)
low = run(True, low_draft, low_draft.init_params(99), label="low")
on = run(True, draft, dp, label="on")    # high-agreement speculation

oracle = [list(sd.eager_generate(target, tp, p, max_new_tokens=NEW))
          for p in prompts]
token_exact = (base["outs"] == oracle and on["outs"] == oracle
               and low["outs"] == oracle)
acceptance = on["accepted"] / max(on["proposed"], 1)
speedup = on["tokens_s"] / max(base["tokens_s"], 1e-9)
low_ratio = low["tokens_s"] / max(base["tokens_s"], 1e-9)

if ENFORCE:
    # the ISSUE-19 acceptance bar, enforced where it is measured
    if not token_exact:
        raise RuntimeError("speculative/baseline outputs diverge from "
                           "the eager oracle under greedy")
    if acceptance < 0.7:
        raise RuntimeError(f"acceptance {acceptance:.2f} < 0.7 on the "
                           "high-agreement draft")
    if speedup < 1.5:
        raise RuntimeError(f"speculative speedup {speedup:.2f}x < 1.5x "
                           f"({on['tokens_s']:.0f} vs "
                           f"{base['tokens_s']:.0f} tok/s)")
    if not low["disabled"]:
        raise RuntimeError("low-agreement draft never auto-disabled")
    if low_ratio < 0.95:
        raise RuntimeError(f"low-agreement leg ran at {low_ratio:.2f}x "
                           "baseline (must stay within 5%: disable "
                           "means degrade, never regress)")

lane = {
    "metric": "decode_speculative_tokens_per_s",
    "value": round(on["tokens_s"], 1),
    "platform": jax.default_backend(),
    "requests": REQS, "new_tokens": NEW, "spec_k": K,
    "baseline_tokens_s": round(base["tokens_s"], 1),
    "spec_tokens_s": round(on["tokens_s"], 1),
    "speedup": round(speedup, 2),
    "acceptance": round(acceptance, 4),
    "rounds": on["rounds"], "proposed": on["proposed"],
    "accepted": on["accepted"], "fallback_rounds": on["fallbacks"],
    "tokens_per_round": round(on["tokens"] / max(on["rounds"], 1), 2),
    "target_dispatches_per_token": round(
        (on["decode_steps"] + on["rounds"]) / max(on["tokens"], 1), 3),
    "low_agreement": {
        "tokens_s": round(low["tokens_s"], 1),
        "ratio_vs_baseline": round(low_ratio, 3),
        "autodisabled": low["disabled"],
        "rounds_before_disable": low["rounds"],
    },
    "token_exact": token_exact,
}
telemetry.flush()   # flight-recorder shard for the lane's fleet merge
lane["telemetry"] = {k: v for k, v in telemetry.snapshot().items() if v}
print(json.dumps(lane))
"""


def run_speculative(requests: int = 12, new_tokens: int = 24,
                    k: int = 4, enforce: bool = True) -> dict:
    env = dict(os.environ)
    env["SPEC_REQUESTS"] = str(requests)
    env["SPEC_NEW_TOKENS"] = str(new_tokens)
    env["SPEC_K"] = str(k)
    env["SPEC_ENFORCE"] = "1" if enforce else "0"
    r = subprocess.run([sys.executable, "-u", "-c", _SPEC_WORKER],
                       capture_output=True, text=True, timeout=900,
                       env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(f"speculative lane failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_shared_prefix(users: int = 16) -> dict:
    env = dict(os.environ)
    env["PREFIX_USERS"] = str(users)
    r = subprocess.run([sys.executable, "-u", "-c", _PREFIX_WORKER],
                       capture_output=True, text=True, timeout=900,
                       env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(f"shared-prefix lane failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_decode(requests: int = 16, concurrency: int = 8,
               storm: bool = True) -> dict:
    env = dict(os.environ)
    env["DECODE_REQUESTS"] = str(requests)
    env["DECODE_CONCURRENCY"] = str(concurrency)
    env["DECODE_STORM"] = "1" if storm else "0"
    r = subprocess.run([sys.executable, "-u", "-c", _DECODE_WORKER],
                       capture_output=True, text=True, timeout=900,
                       env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(f"decode lane failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_serving(requests: int = 64, threads: int = 4) -> dict:
    env = dict(os.environ)
    env["SERVE_REQUESTS"] = str(requests)
    env["SERVE_THREADS"] = str(threads)
    r = subprocess.run([sys.executable, "-u", "-c", _WORKER],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(f"serving lane failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main() -> None:
    as_json = "--json" in sys.argv
    requests = 64
    if "--requests" in sys.argv:
        requests = int(sys.argv[sys.argv.index("--requests") + 1])
    threads = 4
    if "--threads" in sys.argv:
        threads = int(sys.argv[sys.argv.index("--threads") + 1])
    lane = run_serving(requests, threads)
    if as_json:
        print(json.dumps({"serving": lane}))
        return
    print(f"serving latency ({lane['platform']}, {lane['requests']} "
          f"variable-length requests, buckets={lane['buckets']})")
    print(f"programs {lane['programs']} (warm traces "
          f"{lane['warm_traces']}), retraces after warm "
          f"{lane['retraces_after_warm']}, bucket "
          f"{lane['bucket_hits']}h/{lane['bucket_misses']}m")
    print(f"sequential: p50 {lane['p50_us']:.0f} us, p99 "
          f"{lane['p99_us']:.0f} us, {lane['throughput_rps']:.1f} req/s")
    c = lane["concurrent"]
    print(f"concurrent ({c['threads']} threads): "
          f"{c['requests_per_dispatch']:.1f} requests/dispatch "
          f"({c['coalesced']} coalesced), p99 {c['p99_us']:.0f} us, "
          f"{c['throughput_rps']:.1f} req/s")


def main_decode(storm_only: bool = False) -> None:
    lane = run_decode(storm=True)
    print(f"decode lane ({lane['platform']}, {lane['requests']} requests "
          f"x {lane['new_tokens']} tokens, concurrency "
          f"{lane['concurrency']})")
    print(f"programs {lane['programs']} (warmup "
          f"{lane['warmup_programs']}), retraces after warm "
          f"{lane['retraces_after_warm']}, "
          f"{lane['rows_per_decode']} rows/decode-step")
    print(f"one-at-a-time {lane['sequential_tokens_s']} tok/s -> "
          f"continuous {lane['continuous_tokens_s']} tok/s "
          f"({lane['batching_speedup']}x)")
    s = lane.get("storm")
    if s:
        print(f"storm: fast p99 {s['fast']['p99_us']:.0f} us "
              f"(solo {s['fast_solo_p99_us']:.0f} us, "
              f"{s['interference_p99_ratio']}x), "
              f"fast {s['fast']['tokens_s']} tok/s / slow "
              f"{s['slow']['tokens_s']} tok/s, "
              f"{s['shed_total']} shed, "
              f"{s['slow']['preempts'] + s['fast']['preempts']} "
              "preempts")
    r = lane.get("router_storm")
    if r:
        print(f"router storm (1-of-2 replicas killed mid-storm): "
              f"{r['delivered']}/{r['requests']} delivered, "
              f"{r['dropped']} dropped, {r['shed']} shed, "
              f"{r['failed_over']} failed over, {r['hedged']} hedged, "
              f"{r['breaker_transitions']} breaker transitions, "
              f"p99 {r['p99_us']:.0f} us, {r['tokens_s']} tok/s")
    e = lane.get("elastic_storm")
    if e:
        print(f"elastic storm (autoscaler 1->{e['peak_replicas']}->"
              f"{e['final_replicas']} replicas): "
              f"{e['delivered']}/{e['requests']} delivered, "
              f"{e['dropped']} dropped, {e['shed']} shed, "
              f"{e['scale_ups']} up / {e['scale_downs']} down "
              f"({e['scale_errors']} errors, {e['joins']} joins / "
              f"{e['drains']} drains), fleet {e['fleet_tokens_s']} "
              f"tok/s over {e['wall_s']}s, "
              f"{len(e['replica_timeline'])} timeline samples")


def main_spec() -> None:
    lane = run_speculative()
    if "--json" in sys.argv:
        print(json.dumps({"speculative": lane}))
        return
    print(f"speculative decode ({lane['platform']}, {lane['requests']} "
          f"requests x {lane['new_tokens']} tokens, k={lane['spec_k']})")
    print(f"baseline {lane['baseline_tokens_s']} tok/s -> speculative "
          f"{lane['spec_tokens_s']} tok/s ({lane['speedup']}x), "
          f"acceptance {lane['acceptance']:.3f} "
          f"({lane['accepted']}/{lane['proposed']} over "
          f"{lane['rounds']} rounds, "
          f"{lane['tokens_per_round']} tokens/round, "
          f"{lane['target_dispatches_per_token']} target "
          "dispatches/token)")
    lo = lane["low_agreement"]
    print(f"low-agreement draft: auto-disabled after "
          f"{lo['rounds_before_disable']} rounds, "
          f"{lo['tokens_s']} tok/s "
          f"({lo['ratio_vs_baseline']:.2f}x baseline); token-exact vs "
          f"eager oracle: {lane['token_exact']}")


def main_prefix() -> None:
    lane = run_shared_prefix()
    if "--json" in sys.argv:
        print(json.dumps({"prefix": lane}))
        return
    print(f"shared-prefix storm ({lane['platform']}, {lane['users']} users "
          f"x one {lane['prompt_tokens']}-token system prompt)")
    print(f"prefix hit rate {lane['prefix_hit_rate']:.3f} "
          f"({lane['prefix_hit_blocks']}h/{lane['prefix_miss_blocks']}m "
          f"blocks), {lane['prefix_cow_forks']} COW forks, "
          f"{lane['prefix_evictions']} evictions")
    print(f"prefills: warm {lane['prefills_warm']} vs cold "
          f"{lane['prefills_cold']}; {lane['prefill_tokens_saved']} prompt "
          f"tokens ({lane['prefill_flops_saved'] / 1e6:.1f} MFLOPs) of "
          "prefill skipped")
    print(f"throughput: warm {lane['warm_tokens_s_per_chip']} vs cold "
          f"{lane['cold_tokens_s_per_chip']} tok/s/chip; token-exact "
          f"vs cold + eager oracle: {lane['token_exact']}")


if __name__ == "__main__":
    if "--serve-only" in sys.argv:
        # bench.py's lanes[] entry point: the one serving lane
        lane = run_serving()
        print(json.dumps({"serving": lane}) if "--json" in sys.argv
              else lane)
    elif "--decode-only" in sys.argv:
        # bench.py's decode lane entry point
        lane = run_decode()
        print(json.dumps({"decode": lane}) if "--json" in sys.argv
              else lane)
    elif "--shared-prefix" in sys.argv:
        # ISSUE-16 lane: M users x one system prompt through the
        # content-addressed prefix cache, warm vs cold vs eager oracle
        main_prefix()
    elif "--speculative" in sys.argv:
        # ISSUE-19 lane: spec on (high-agreement draft) vs the non-spec
        # baseline on the same prompt set, plus the low-agreement
        # auto-disable leg — acceptance bars enforced in the worker
        main_spec()
    elif "--storm" in sys.argv:
        main_decode(storm_only=True)
    else:
        main()
