"""Speculative decoding + in-program stochastic sampling (ISSUE 19
tentpole, ``mxnet_tpu/serving_decode.py``).

Pins: (1) the in-program sampler — temperature / top-k / top-p ride
the ONE fixed-shape decode program as traced per-row operands, every
grid point seed-for-seed identical to the ``eager_generate`` oracle,
``temperature == 0`` bit-identical to the plain argmax, heterogeneous
configs sharing one program with 0 retraces; (2) the counter-based
PRNG — ``fold_in(PRNGKey(seed), position)`` makes replay positional,
so retries and cross-host dispatch are token-exact; (3) speculative
decoding (``MXNET_SPEC_DECODE``) — the high-agreement pair decodes
token-exact under greedy while committing k tokens per verify
dispatch, a low-agreement draft trips the sticky auto-disable and the
stream STAYS token-exact, and the knob off means ZERO spec dispatches
even with a draft attached; (4) the sampling spec over the
``serving_remote`` wire; and (5) the dispatch-budget spec lane + the
``spec_draft_poison`` chaos cell run end-to-end by the tool gates.
"""
import functools
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401  (jax/backend init via conftest)
from mxnet_tpu import engine as _engine
from mxnet_tpu import serving_decode as sd


@functools.lru_cache(maxsize=None)
def _tiny_cached(seed):
    model = sd.TinyCausalLM(vocab=31, d_model=16, n_layers=2,
                            n_heads=2, max_seq=32)
    return model, model.init_params(seed)


@functools.lru_cache(maxsize=None)
def _pair_cached(seed=0):
    """Module-shared high-agreement (target, draft) fixture — same
    geometry as the plain-decode tests so warm programs are reused
    across the file."""
    return sd.high_agreement_pair(vocab=31, d_model=16,
                                  target_layers=2, draft_layers=1,
                                  n_heads=2, max_seq=32, seed=seed)


def _mk(model, params, pages=64, page=4, max_rows=4, warm=8,
        name="spec", **kw):
    pool = sd.PagePool(pages=pages, page=page)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=max_rows, name=name, **kw)
    if warm:
        eng.warmup(max_len=warm)
    return eng, pool


# ---------------------------------------------------------------------------
# SamplingSpec surface
# ---------------------------------------------------------------------------
def test_sampling_spec_validation_and_wire_roundtrip():
    s = sd.SamplingSpec(temperature=0.8, top_k=5, top_p=0.9, seed=7)
    assert not s.greedy
    assert sd.SamplingSpec.from_wire(s.to_wire()) == s
    import json
    json.dumps(s.to_wire())                     # frame-protocol safe
    assert sd.GREEDY.greedy and sd.SamplingSpec().greedy
    with pytest.raises(ValueError):
        sd.SamplingSpec(temperature=-0.1)
    with pytest.raises(ValueError):
        sd.SamplingSpec(temperature=float("inf"))
    with pytest.raises(ValueError):
        sd.SamplingSpec(top_p=0.0)
    with pytest.raises(ValueError):
        sd.SamplingSpec(top_p=1.5)
    # seeds coerce into PRNGKey space identically everywhere
    assert sd.SamplingSpec(seed=-1).seed == sd.SamplingSpec(
        seed=-1).to_wire()["seed"]


def test_generate_rejects_non_spec_sampling():
    model, params = _tiny_cached(0)
    eng, pool = _mk(model, params, warm=0, name="val")
    with eng:
        with pytest.raises(TypeError):
            eng.generate([1, 2], max_new_tokens=2,
                         sampling={"temperature": 1.0})


# ---------------------------------------------------------------------------
# In-program sampling: compiled vs eager, seed-for-seed, every grid point
# ---------------------------------------------------------------------------
def test_sampled_decode_parity_grid_vs_eager_oracle():
    """The tentpole's layer-1 acceptance bar: for EVERY
    (temperature, top_k, top_p) grid point the batched engine's output
    is seed-for-seed identical to the eager oracle — same sampler, same
    counter-based keys, different program."""
    model, params = _tiny_cached(11)
    eng, pool = _mk(model, params, name="grid")
    grid = [(t, k, p) for t in (0.0, 0.8, 1.5)
            for k in (0, 4) for p in (1.0, 0.85)]
    prompt = [3, 5, 7]
    with eng:
        for i, (t, k, p) in enumerate(grid):
            samp = sd.SamplingSpec(temperature=t, top_k=k, top_p=p,
                                   seed=100 + i)
            got = eng.generate(prompt, max_new_tokens=4, sampling=samp)
            ref = sd.eager_generate(model, params, prompt, 4,
                                    sampling=samp)
            assert got == ref, (t, k, p)
    assert pool.in_use() == 0


def test_temperature_zero_is_bit_exact_greedy():
    """A greedy request through the sampling-capable program decodes
    exactly as before: sampling=None, an all-default SamplingSpec, and
    temperature-0 with active filters all land on the argmax chain."""
    model, params = _tiny_cached(12)
    eng, pool = _mk(model, params, name="t0")
    prompt = [9, 2, 4, 1]
    with eng:
        plain = eng.generate(prompt, max_new_tokens=5)
        for samp in (sd.GREEDY,
                     sd.SamplingSpec(temperature=0.0, top_k=3,
                                     top_p=0.5, seed=999)):
            assert eng.generate(prompt, max_new_tokens=5,
                                sampling=samp) == plain
    assert plain == sd.eager_generate(model, params, prompt, 5)


def test_sampling_positional_replay_and_seed_sensitivity():
    """Determinism is positional: the same (seed, prompt) replays the
    SAME tokens (the retry/failover/hedge story), while a different
    seed diverges (it is actually sampling)."""
    model, params = _tiny_cached(13)
    eng, pool = _mk(model, params, name="replay")
    prompt = [1, 2, 3]
    with eng:
        a = eng.generate(prompt, max_new_tokens=6,
                         sampling=sd.SamplingSpec(1.2, seed=5))
        b = eng.generate(prompt, max_new_tokens=6,
                         sampling=sd.SamplingSpec(1.2, seed=5))
        assert a == b
        outs = {tuple(eng.generate(prompt, max_new_tokens=6,
                                   sampling=sd.SamplingSpec(1.2,
                                                            seed=s)))
                for s in range(8)}
    assert len(outs) > 1                        # seeds matter


def test_mixed_sampling_configs_share_programs_zero_retraces():
    """Heterogeneous sampling configs ride ONE program set: after
    warm-up a concurrent mix of greedy and wildly different sampled
    requests adds 0 traces and 0 programs."""
    model, params = _tiny_cached(14)
    eng, pool = _mk(model, params, name="mix")
    grid = eng.stats()["programs"]
    t0 = sd.trace_count()
    samps = [None,
             sd.SamplingSpec(0.7, top_k=3, seed=1),
             sd.SamplingSpec(1.5, top_p=0.8, seed=2),
             sd.SamplingSpec(0.0),
             sd.SamplingSpec(2.0, top_k=9, top_p=0.6, seed=3)]
    res = [None] * len(samps)

    def fire(i):
        res[i] = eng.generate([4 + i, 5], max_new_tokens=4,
                              sampling=samps[i])

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(len(samps))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, samp in enumerate(samps):
        assert res[i] == sd.eager_generate(model, params, [4 + i, 5],
                                           4, sampling=samp), i
    assert sd.trace_count() - t0 == 0
    assert eng.stats()["programs"] == grid
    assert pool.in_use() == 0
    eng.close()


# ---------------------------------------------------------------------------
# The sampling spec over the serving_remote wire (satellite: router +
# remote protocol carry per-request sampling end-to-end)
# ---------------------------------------------------------------------------
def test_router_failover_replays_sampled_request_token_exact():
    """A failed-over SAMPLED request replays token-exact: the seed +
    committed positions ride the re-dispatch (like t_enqueue), and the
    counter-based PRNG makes the replica swap invisible — same tokens
    as the uninterrupted eager oracle."""
    from mxnet_tpu import faults
    from mxnet_tpu.serving_router import ReplicaRouter

    model, params = _tiny_cached(17)
    engines, pools = [], []
    for i in range(2):
        eng, pool = _mk(model, params, pages=32, page=4, max_rows=2,
                        name=f"fo{i}")
        engines.append(eng)
        pools.append(pool)
    router = ReplicaRouter(engines, breaker_errs=2,
                           breaker_cooldown_s=0.2)
    samp = sd.SamplingSpec(temperature=1.0, top_k=6, top_p=0.9,
                           seed=77)
    try:
        with faults.active(faults.FaultPlan().fail("router.dispatch",
                                                   times=1)):
            out = router.generate([2, 4, 6], max_new_tokens=5,
                                  sampling=samp)
        assert out == sd.eager_generate(model, params, [2, 4, 6], 5,
                                        sampling=samp)
    finally:
        for eng in engines:
            eng.close()
    _engine.waitall()
    assert all(p.in_use() == 0 for p in pools)


def test_remote_sampled_parity_seed_for_seed():
    from mxnet_tpu import serving_remote as srm

    model, params = _tiny_cached(15)
    eng, pool = _mk(model, params, max_rows=2, name="wire-s")
    srv = srm.ReplicaServer(eng).start()
    try:
        rr = srm.RemoteReplica("127.0.0.1", srv.port)
        samp = sd.SamplingSpec(temperature=0.9, top_k=5, top_p=0.9,
                               seed=42)
        out = rr.generate([4, 5, 6], max_new_tokens=5, sampling=samp)
        assert out == sd.eager_generate(model, params, [4, 5, 6], 5,
                                        sampling=samp)
        # greedy default unchanged: no sampling field → argmax chain
        assert rr.generate([4, 5, 6], max_new_tokens=3) == \
            sd.eager_generate(model, params, [4, 5, 6], 3)
    finally:
        srv.close()
    _engine.waitall()
    assert pool.in_use() == 0


# ---------------------------------------------------------------------------
# Speculative decoding (MXNET_SPEC_DECODE)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def spec_engine():
    """ONE warmed high-agreement spec engine shared by the knob-off /
    greedy / sampled pins below (tier-1 wall guard: the spec program
    grid traces once, not once per test).  The knob is read per
    REQUEST, so tests flip MXNET_SPEC_DECODE around individual
    generate() calls."""
    target, tp, draft, dp = _pair_cached()
    eng, pool = _mk(target, tp, name="spec-hi", draft=draft,
                    draft_params=dp, spec_k=4)
    yield eng, pool, target, tp
    eng.close()


def test_spec_off_by_default_zero_spec_dispatches(spec_engine,
                                                  monkeypatch):
    """A draft attached but the knob unset means plain decode at serve
    time: warmup still pre-compiles the spec grid (so a later knob
    flip is free), but ZERO spec traces/dispatches happen for real
    traffic and the tokens are identical to the draftless chain."""
    monkeypatch.delenv("MXNET_SPEC_DECODE", raising=False)
    eng, pool, target, tp = spec_engine
    st0, sd0 = sd.spec_trace_count(), sd.spec_dispatch_count()
    rounds0 = eng.stats()["spec_rounds"]
    out = eng.generate([2, 7, 1], max_new_tokens=5)
    assert out == sd.eager_generate(target, tp, [2, 7, 1], 5)
    assert eng.stats()["spec_rounds"] == rounds0
    assert sd.spec_trace_count() - st0 == 0      # post-warmup serve path
    assert sd.spec_dispatch_count() - sd0 == 0
    assert pool.in_use() == 0


def test_spec_greedy_token_exact_high_agreement(spec_engine,
                                                monkeypatch):
    """The tentpole's layer-2 acceptance bar: with the knob on and the
    agreeing draft, greedy decode is token-exact vs the target-only
    oracle while speculation actually runs — rounds > 0, acceptance
    1.0 by construction, multiple tokens per verify dispatch."""
    monkeypatch.setenv("MXNET_SPEC_DECODE", "1")
    eng, pool, target, tp = spec_engine
    prompts = [[3, 5, 7], [1], [8, 2, 9, 4]]
    budgets = [8, 6, 7]
    res = [None] * 3

    def fire(i):
        res[i] = eng.generate(prompts[i], max_new_tokens=budgets[i])

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(3):
        assert res[i] == sd.eager_generate(target, tp, prompts[i],
                                           budgets[i]), f"request {i}"
    st = eng.stats()
    assert st["spec_rounds"] > 0 and not st["spec_disabled"]
    assert st["spec_accepted"] == st["spec_proposed"]    # 1.0
    # the k-for-1 economics: committed tokens per verify dispatch > 1
    assert st["spec_accepted"] > 0
    assert st["spec_programs"] > 0
    assert pool.in_use() == 0                            # BOTH geometries


def test_spec_sampled_lane_runs_and_temp_zero_stays_exact(spec_engine,
                                                          monkeypatch):
    """Sampling through the spec lane: a temperature-0 SamplingSpec
    (with active filters) rides the rejection-sampling verify programs
    and STAYS bit-exact with the plain greedy chain — the 0-branch
    degenerates to the argmax accept test — while a hot-temperature
    spec actually speculates and emits in-vocab tokens.  (Stochastic
    outputs are distributionally the target's, not positionally
    replayable: which positions land as proposal / resample / bonus
    depends on the cost-table arbitration, so only greedy pins
    token-for-token.)"""
    monkeypatch.setenv("MXNET_SPEC_DECODE", "1")
    eng, pool, target, tp = spec_engine
    g0 = eng.generate([6, 3], max_new_tokens=6,
                      sampling=sd.SamplingSpec(temperature=0.0,
                                               top_k=5, top_p=0.7,
                                               seed=31))
    assert g0 == sd.eager_generate(target, tp, [6, 3], 6)
    hot = eng.generate([6, 3], max_new_tokens=6,
                       sampling=sd.SamplingSpec(temperature=1.1,
                                                top_k=7, top_p=0.95,
                                                seed=31))
    assert len(hot) == 6 and all(0 <= t < 31 for t in hot)
    assert eng.stats()["spec_rounds"] > 0
    assert pool.in_use() == 0


def test_spec_low_agreement_auto_disables_stream_stays_exact(
        monkeypatch):
    """The degrade path: an independent (disagreeing) draft trips the
    sticky low-acceptance cutoff after the probation rounds — the
    spec.autodisabled counter ticks, the engine falls back to plain
    decode IN-PLACE, and the greedy stream was token-exact the whole
    time (rejection sampling never commits a wrong token)."""
    monkeypatch.setenv("MXNET_SPEC_DECODE", "1")
    target, tp = _tiny_cached(16)
    low = sd.TinyCausalLM(vocab=31, d_model=16, n_layers=1, n_heads=2,
                          max_seq=32)
    lp = low.init_params(77)
    before = sd._SPEC_STATS["autodisabled"]
    eng, pool = _mk(target, tp, name="spec-lo", draft=low,
                    draft_params=lp, spec_k=4)
    with eng:
        out = eng.generate([5, 1, 3], max_new_tokens=12)
    assert out == sd.eager_generate(target, tp, [5, 1, 3], 12)
    st = eng.stats()
    assert st["spec_disabled"] is True
    assert st["spec_rounds"] >= 4                # probation ran
    assert st["spec_accepted"] < st["spec_proposed"]
    assert sd._SPEC_STATS["autodisabled"] == before + 1
    assert pool.in_use() == 0


def test_spec_requires_decode_chunk_and_matching_vocab():
    target, tp, draft, dp = _pair_cached()
    pool = sd.PagePool(pages=8, page=4)
    other = sd.TinyCausalLM(vocab=13, d_model=16, n_layers=1,
                            n_heads=2, max_seq=32)
    with pytest.raises(ValueError):
        sd.GenerativeEngine(target, params=tp, pool=pool, name="v",
                            draft=other, draft_params=other.init_params())


# ---------------------------------------------------------------------------
# Tool-gate lanes (the full gates run as slow subprocess tests)
# ---------------------------------------------------------------------------
def test_dispatch_budget_spec_lane_in_process():
    """The CI gate's spec lane: bounded program set over BOTH
    namespaces, 0 retraces across mixed sampled/greedy traffic,
    target dispatches amortized below 1/token, greedy rows token-exact,
    and the knob-off leg byte-identical to a draftless engine."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_dispatch_budget",
        os.path.join(root, "tools", "check_dispatch_budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    d = mod._measure_spec()
    assert not d["errors"]
    for key, budget in mod.SPEC_BUDGET.items():
        assert d[key] <= budget, (key, d)
    assert d["spec_rounds"] > 0 and not d["spec_disabled"]
    assert d["acceptance"] >= 0.7
    assert d["target_dispatches_per_token"] < 1.0
    assert d["greedy_token_exact"]
    assert d["greedy_off_outputs_equal"]


def test_availability_gate_spec_draft_poison_scenario():
    """The chaos cell end-to-end as a real subprocess drill: a draft
    poisoned mid-round auto-disables speculation on BOTH replicas with
    0 dropped requests, token-exact streams, and a clean page audit."""
    import tools.check_availability_budget as gate

    assert gate.main(["spec_draft_poison"]) == 0
