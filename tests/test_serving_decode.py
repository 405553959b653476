"""Continuous-batching generative serving (PR 8 tentpole,
``mxnet_tpu/serving_decode.py``).

Pins: (1) the paged KV-cache allocator (alloc/free/reuse, typed
exhaustion, no aliasing via the poisoned-page canary), (2) greedy
decode through the continuous batcher token-exact vs the one-request
eager loop — including a sequence joining mid-stream, one retiring
early, and a pool-pressure preemption, (3) the admission controller's
typed ``ShedError`` refusals (queue / pool / SLO / injected
``serving.admit`` fault) — overload NEVER times out, (4) the bounded
program set (prefill buckets + 1 decode; warm-up idempotent; 0
steady-state retraces; dispatches == decode iterations + prefills),
and (5) the per-model stats surface plus the dispatch-budget ``decode``
lane run end-to-end by the tool gate.
"""
import functools
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401  (jax/backend init via conftest)
from mxnet_tpu import engine as _engine
from mxnet_tpu import faults
from mxnet_tpu import serving_decode as sd


def tiny(seed=0, **kw):
    """Module-shared model/params (ISSUE-17 wall slice 2): TinyCausalLM
    is stateless config and the param pytree is immutable jax arrays,
    so every test sharing a (seed, cfg) reuses ONE instance instead of
    re-initializing per test."""
    return _tiny_cached(seed, tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _tiny_cached(seed, kw_items):
    cfg = dict(vocab=31, d_model=16, n_layers=2, n_heads=2, max_seq=32)
    cfg.update(dict(kw_items))
    model = sd.TinyCausalLM(**cfg)
    return model, model.init_params(seed)


# ---------------------------------------------------------------------------
# PagePool allocator
# ---------------------------------------------------------------------------
def test_pagepool_alloc_free_reuse():
    pool = sd.PagePool(pages=4, page=2)
    a = pool.alloc(2)
    b = pool.alloc(1)
    assert len(set(a) | set(b)) == 3 and pool.in_use() == 3
    pool.free(a)
    assert pool.in_use() == 1 and pool.free_pages() == 3
    # LIFO reuse: the just-freed (hot) pages come back first
    c = pool.alloc(2)
    assert set(c) == set(a) and pool.in_use() == 3
    st = pool.stats()
    assert st["alloc_count"] == 5 and st["free_count"] == 2
    assert st["high_water"] == 3


def test_pagepool_exhaustion_is_typed_shed():
    pool = sd.PagePool(pages=2, page=4)
    pool.alloc(2)
    with pytest.raises(sd.PagePoolExhausted) as ei:
        pool.alloc(1)
    assert isinstance(ei.value, sd.ShedError)       # the faults taxonomy
    assert isinstance(ei.value, faults.ShedError)
    assert pool.stats()["exhausted_count"] == 1


def test_pagepool_double_free_raises():
    pool = sd.PagePool(pages=2, page=2)
    pages = pool.alloc(1)
    pool.free(pages)
    with pytest.raises(ValueError):
        pool.free(pages)


def test_pagepool_trash_page_reserved():
    pool = sd.PagePool(pages=3, page=2)
    got = pool.alloc(3)
    assert pool.trash not in got        # index `pages` is never handed out


# ---------------------------------------------------------------------------
# Decode parity: continuous batcher vs the eager single-sequence loop
# ---------------------------------------------------------------------------
def test_single_sequence_token_exact():
    model, params = tiny()
    pool = sd.PagePool(pages=32, page=4)
    with sd.GenerativeEngine(model, params=params, pool=pool,
                             max_rows=4, name="m") as eng:
        eng.warmup(max_len=16)
        for prompt, n in (([3, 5, 7], 6), ([1], 8), (list(range(11)), 4)):
            assert eng.generate(prompt, max_new_tokens=n) == \
                sd.eager_generate(model, params, prompt, n)
        assert pool.in_use() == 0


def test_join_retire_storm_token_exact_and_bounded_programs():
    """Sequences join mid-stream and retire early; every result must be
    token-exact and the program set must stay prefill-buckets + 1 with
    0 retraces after warm-up."""
    model, params = tiny(seed=1)
    pool = sd.PagePool(pages=64, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=3, name="m")
    grid = eng.warmup(max_len=16)
    assert grid == 6                    # pow2 buckets 1,2,4,8,16 + decode
    t0, d0 = sd.trace_count(), sd.dispatch_count()
    rng = onp.random.RandomState(5)
    prompts = [rng.randint(0, 31, size=rng.randint(1, 12)).tolist()
               for _ in range(6)]
    budgets = [2, 7, 3, 6, 5, 8]        # early retires force mid-stream
    results = [None] * 6                # joins into freed rows

    def fire(i, delay):
        time.sleep(delay)
        results[i] = eng.generate(prompts[i], max_new_tokens=budgets[i])

    threads = [threading.Thread(target=fire, args=(i, 0.01 * (i // 2)))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(6):
        assert results[i] == sd.eager_generate(
            model, params, prompts[i], budgets[i]), f"request {i}"
    st = eng.stats()
    assert sd.trace_count() - t0 == 0                     # 0 retraces
    assert st["programs"] == grid                         # bounded set
    # 1 dispatch per decode iteration + 1 per prefill, nothing else
    assert sd.dispatch_count() - d0 == \
        st["decode_steps"] + st["prefills"]
    assert st["prefills"] >= 6                            # every join
    assert pool.in_use() == 0                             # 0 leaks
    eng.close()


def test_poisoned_free_pages_do_not_alias_live_sequences():
    """The aliasing canary: retire one sequence, overwrite every FREE
    page with garbage while another is mid-decode — if any live row
    ever reads a page it does not own, its tokens diverge."""
    model, params = tiny(seed=2)
    pool = sd.PagePool(pages=32, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=2, name="m")
    eng.warmup(max_len=8)
    res = {}

    def short():
        res["a"] = eng.generate([2, 3, 4], max_new_tokens=2)

    def long():
        res["b"] = eng.generate([5, 6], max_new_tokens=10)

    ta, tb = threading.Thread(target=short), threading.Thread(target=long)
    ta.start()
    tb.start()
    ta.join()                           # a retired, its pages are free
    n = pool.poison_free(1e30)
    tb.join()
    assert n > 0
    assert res["a"] == sd.eager_generate(model, params, [2, 3, 4], 2)
    assert res["b"] == sd.eager_generate(model, params, [5, 6], 10)
    eng.close()


def test_preemption_under_pool_pressure_token_exact():
    """A pool too small for two full sequences forces a preempt: the
    youngest is evicted (pages freed, request re-queued) and its
    recomputed greedy continuation must stay token-exact."""
    model, params = tiny(seed=3)
    pool = sd.PagePool(pages=4, page=2)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=2, name="m")
    eng.warmup(max_len=8)
    prompts, res = [[1, 2, 3], [4, 5]], {}

    def fire(i):
        res[i] = eng.generate(prompts[i], max_new_tokens=4)

    threads = [threading.Thread(target=fire, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in (0, 1):
        assert res[i] == sd.eager_generate(model, params, prompts[i], 4)
    assert eng.stats()["preempts"] >= 1
    assert pool.in_use() == 0
    eng.close()


def test_preemption_keeps_enqueue_clock_and_seniority():
    """ISSUE-14 satellite: a request re-queued by mid-decode preemption
    keeps (1) its original ``t_enqueue`` — the queue-wait clock never
    resets, so p99 stays honest — and (2) its original admission-order
    stamp, so youngest-first preemption targets a TRULY younger
    arrival next time instead of re-victimizing the preempted request
    forever."""
    model, params = tiny(seed=4)
    pool = sd.PagePool(pages=64, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=4, name="sen")
    eng.warmup(max_len=8)
    # white-box: drive the scheduler's own entry points synchronously
    r1 = sd._GenRequest([1, 2, 3], 12, None)
    r2 = sd._GenRequest([4, 5], 12, None)
    eng._prefill(r1)
    eng._prefill(r2)
    assert (r1.joined, r2.joined) == (0, 1)
    t_orig = r2.t_enqueue
    row2 = next(r for r in eng._live if r.req is r2)
    eng._preempt(row2)                    # mid-decode eviction
    assert r2.preempts == 1
    assert r2.t_enqueue == t_orig         # clock NOT reset
    # a genuinely newer arrival prefills while r2 waits re-queued
    r3 = sd._GenRequest([6, 7], 12, None)
    eng._prefill(r3)
    assert r3.joined == 2
    with eng._cv:
        eng._queue.remove(r2)
    eng._prefill(r2)                      # the re-queue's re-prefill
    assert r2.joined == 1                 # original seniority KEPT
    assert r2.t_enqueue == t_orig
    # youngest-first preemption now picks r3 (joined 2), never r2
    rows = {r.req: r for r in eng._live}
    victims = [x for x in eng._live if x is not rows[r1]]
    assert max(victims, key=lambda x: x.joined).req is r3
    for row in list(eng._live):
        eng._live.remove(row)
        eng._release(row)
    assert pool.in_use() == 0
    eng.close()


def test_eos_stops_generation():
    model, params = tiny(seed=4)
    prompt = [7, 9]
    ref = sd.eager_generate(model, params, prompt, 8)
    eos = ref[2]                        # force a mid-stream stop
    pool = sd.PagePool(pages=16, page=4)
    with sd.GenerativeEngine(model, params=params, pool=pool,
                             max_rows=2, name="m") as eng:
        out = eng.generate(prompt, max_new_tokens=8, eos=eos)
    assert out == sd.eager_generate(model, params, prompt, 8, eos=eos)
    assert out[-1] == eos and len(out) <= 8


# ---------------------------------------------------------------------------
# Admission control: typed sheds, never a timeout (site serving.admit)
# ---------------------------------------------------------------------------
def test_admission_injected_fault_sheds():
    model, params = tiny()
    pool = sd.PagePool(pages=8, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool, name="m")
    with faults.active(faults.FaultPlan().fail("serving.admit", times=1)):
        with pytest.raises(sd.ShedError):
            eng.generate([1, 2], max_new_tokens=2)
    evs = faults.events("serving.admit")
    assert any(e["action"] == "shed" for e in evs)
    assert eng.stats()["shed"] == 1
    eng.close()


def test_admission_queue_full_sheds():
    model, params = tiny()
    pool = sd.PagePool(pages=8, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_queue=2, name="m")
    eng._queue.extend([object(), object()])      # saturated backlog
    t0 = time.monotonic()
    with pytest.raises(sd.ShedError) as ei:
        eng.generate([1, 2], max_new_tokens=2)
    assert time.monotonic() - t0 < 1.0           # fail FAST, no timeout
    assert "queue full" in str(ei.value)
    assert eng.stats()["shed_queue"] == 1
    eng._queue.clear()
    eng.close()


def test_admission_pool_never_fits_sheds():
    model, params = tiny()
    pool = sd.PagePool(pages=2, page=2)          # 4 token capacity
    eng = sd.GenerativeEngine(model, params=params, pool=pool, name="m")
    with pytest.raises(sd.ShedError) as ei:
        eng.generate([1] * 8, max_new_tokens=4)
    assert "never fit" in str(ei.value)
    assert eng.stats()["shed_pool"] == 1
    eng.close()


def test_admission_slo_cost_table_sheds():
    """SLO-aware admission prices the request from the measured cost
    table (no trial dispatch): with a primed decode EMA and a queued
    backlog the estimate busts the SLO and the request sheds."""
    model, params = tiny()
    pool = sd.PagePool(pages=8, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              slo_us=10, name="m")
    eng._cost["decode"] = 1.0                    # 1 s/step measured
    eng._queue.append(object())
    with pytest.raises(sd.ShedError) as ei:
        eng.generate([1, 2], max_new_tokens=5)
    assert "SLO" in str(ei.value)
    assert eng.stats()["shed_slo"] == 1
    eng._queue.clear()
    eng.close()


def test_shed_is_not_retryable():
    assert not faults.is_retryable(sd.ShedError("x"))


# ---------------------------------------------------------------------------
# Warm-up, program set, stats, drain
# ---------------------------------------------------------------------------
def test_warmup_grid_and_idempotence():
    model, params = tiny()
    pool = sd.PagePool(pages=16, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool, name="m")
    n = eng.warmup(max_len=8)
    assert n == 5                       # buckets 1,2,4,8 + decode
    assert eng.warmup(max_len=8) == 0   # idempotent
    assert eng.stats()["programs"] == 5
    # warm programs are HIT, not re-traced, by the first real request
    t0 = sd.trace_count()
    out = eng.generate([1, 2, 3], max_new_tokens=2)
    assert len(out) == 2 and sd.trace_count() == t0
    eng.close()


def test_stats_surface_and_latency_percentiles():
    model, params = tiny()
    pool = sd.PagePool(pages=16, page=4)
    with sd.GenerativeEngine(model, params=params, pool=pool,
                             name="modelA") as eng:
        eng.warmup(max_len=8)
        eng.generate([1, 2], max_new_tokens=3)
        st = eng.stats()
    assert st["model"] == "modelA"
    for key in ("p50_us", "p99_us", "shed", "shed_queue", "shed_pool",
                "shed_slo", "preempts", "slo_violations", "tokens_out",
                "decode_steps", "prefills", "delivered", "pool"):
        assert key in st, key
    assert st["p50_us"] > 0 and st["delivered"] == 1
    assert st["tokens_out"] + 1 >= 3    # prefill token + decode tokens


def test_waitall_drains_engine():
    model, params = tiny()
    pool = sd.PagePool(pages=16, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool, name="m")
    eng.warmup(max_len=8)
    done = []
    t = threading.Thread(
        target=lambda: done.append(
            eng.generate([1, 2], max_new_tokens=6)))
    t.start()
    deadline = time.monotonic() + 10.0  # wait until the engine has it
    while eng.stats()["prefills"] == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    _engine.waitall()                   # must block until delivered
    with eng._cv:
        assert not eng._queue and not eng._live
    t.join()
    assert len(done[0]) == 6 and pool.in_use() == 0
    eng.close()


def test_multi_model_shared_pool_accounting():
    """Two engines (distinct geometries) draw pages from ONE pool; both
    decode concurrently, results stay token-exact, and the shared
    accounting returns to zero."""
    m1, p1 = tiny(seed=6)
    m2 = sd.TinyCausalLM(vocab=31, d_model=24, n_layers=1, n_heads=3,
                         max_seq=32)
    p2 = m2.init_params(7)
    pool = sd.PagePool(pages=32, page=4)
    e1 = sd.GenerativeEngine(m1, params=p1, pool=pool, max_rows=2,
                             name="a")
    e2 = sd.GenerativeEngine(m2, params=p2, pool=pool, max_rows=2,
                             name="b")
    e1.warmup(max_len=8)
    e2.warmup(max_len=8)
    res = {}
    threads = [
        threading.Thread(target=lambda: res.setdefault(
            "a", e1.generate([1, 2, 3], max_new_tokens=5))),
        threading.Thread(target=lambda: res.setdefault(
            "b", e2.generate([4, 5], max_new_tokens=6))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert res["a"] == sd.eager_generate(m1, p1, [1, 2, 3], 5)
    assert res["b"] == sd.eager_generate(m2, p2, [4, 5], 6)
    assert pool.in_use() == 0
    assert pool.stats()["high_water"] >= 2      # both were live at once
    e1.close()
    e2.close()


def test_generate_validates_inputs():
    model, params = tiny()
    pool = sd.PagePool(pages=8, page=4)
    with sd.GenerativeEngine(model, params=params, pool=pool,
                             name="m") as eng:
        with pytest.raises(ValueError):
            eng.generate([], max_new_tokens=2)
        with pytest.raises(ValueError):
            eng.generate([1], max_new_tokens=0)
        with pytest.raises(ValueError):          # beyond model.max_seq
            eng.generate(list(range(30)), max_new_tokens=10)


def test_dispatch_budget_tool_decode_lane():
    """The CI gate's decode lane (tools/check_dispatch_budget.py,
    loaded like check_fault_sites; the FULL gate runs in
    test_serving.py): join/retire storm inside every budget —
    programs == grid, 0 retraces, 1 dispatch/iteration, 0 leaks."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_dispatch_budget",
        os.path.join(root, "tools", "check_dispatch_budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    d = mod._measure_decode()
    assert not d["errors"] and d["shed"] == 0
    for key, budget in mod.DECODE_BUDGET.items():
        assert d[key] <= budget, (key, d)
    assert d["rows_per_decode"] > 1     # it actually batched
