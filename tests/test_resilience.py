"""The resilience layer + deterministic chaos suite (PR 8).

Unit coverage for :mod:`repro.api.resilience` (policy JSON round-trip,
seeded backoff, circuit-breaker lifecycle) and fault-injected coverage for
every recovery path in the serving stack: load shedding, deadlines at both
enforcement points, worker crash -> supervisor restart -> budget
exhaustion, predict retry, breaker-driven backend fallback with parity,
failed hot-swap leaving the old version serving, and the shutdown TOCTOU
race.  The invariant every scenario asserts through
:class:`~repro.fleet.faults.FutureLedger`: **no injected fault ever
strands a future** — each resolves with a result or a typed exception.

CI runs this file as the ``chaos-smoke`` job.
"""

import threading
import time

import numpy as np
import pytest

from repro.api import (
    BadRequest,
    CircuitBreaker,
    DeadlineExceeded,
    EngineStats,
    EngineStopped,
    GBDTEngine,
    MicroBatchEngine,
    Overloaded,
    ResiliencePolicy,
    ToadModel,
    WorkerCrashed,
    backoff_delays,
    fallback_chain,
)
from repro.fleet import (
    Fault,
    FaultPlan,
    FleetEngine,
    FutureLedger,
    InjectedFault,
    ModelRegistry,
)

rng = np.random.default_rng


def _sum_fn(X):
    return np.asarray(X).sum(axis=1, keepdims=True)


def _mk_engine(fn=_sum_fn, d=4, **kw):
    return MicroBatchEngine(fn, d, **kw)


def _rows(n, d=4, seed=0):
    return rng(seed).normal(size=(n, d)).astype(np.float32)


# ---------------------------------------------------------------- policy
def test_policy_json_roundtrip():
    p = ResiliencePolicy(max_queue_depth=32, deadline_ms=50.0, max_retries=2,
                         seed=7, breaker_threshold=5, restart_budget=1)
    assert ResiliencePolicy.from_json(p.to_json()) == p
    assert ResiliencePolicy.from_dict(p.to_dict()) == p
    with pytest.raises(ValueError, match="unknown ResiliencePolicy field"):
        ResiliencePolicy.from_dict({"max_queue_depth": 1, "typo_field": 2})


def test_backoff_deterministic_and_exponential():
    p = ResiliencePolicy(max_retries=4, backoff_base_ms=10.0,
                         backoff_mult=2.0, backoff_jitter=0.5, seed=3)
    a, b = list(backoff_delays(p)), list(backoff_delays(p))
    assert a == b and len(a) == 4          # same seed -> same schedule
    assert list(backoff_delays(ResiliencePolicy(max_retries=4, seed=4))) != a
    for i, d in enumerate(a):              # base*mult**i <= d <= that*(1+j)
        lo = 0.010 * 2.0**i
        assert lo <= d <= lo * 1.5


# ---------------------------------------------------------------- breaker
def test_breaker_lifecycle_with_fake_clock():
    t = [0.0]
    br = CircuitBreaker(threshold=3, cooldown_s=1.0, clock=lambda: t[0])
    assert br.state == "closed" and br.allow()
    br.record_failure(); br.record_failure()
    assert br.state == "closed"            # consecutive failures below N
    br.record_success()
    br.record_failure(); br.record_failure()
    assert br.state == "closed"            # success reset the streak
    br.record_failure()
    assert br.state == "open" and not br.allow()
    t[0] = 1.5                             # cooldown elapsed
    assert br.state == "half_open"
    assert br.allow()                      # the single probe is claimed...
    assert not br.allow()                  # ...concurrent callers blocked
    br.record_failure()                    # probe failed: reopen
    assert br.state == "open"
    t[0] = 3.0
    assert br.allow()
    br.record_success()                    # probe succeeded: closed
    assert br.state == "closed" and br.allow()
    br.trip()
    assert br.state == "open"


# ------------------------------------------------------- typed admission
def test_submit_before_start_and_after_stop_typed():
    eng = _mk_engine()
    with pytest.raises(EngineStopped):
        eng.submit(np.zeros(4, np.float32))
    eng.start()
    assert eng.submit(np.zeros(4, np.float32)).result(5).shape == (1,)
    eng.stop()
    with pytest.raises(EngineStopped):
        eng.submit(np.zeros(4, np.float32))
    assert isinstance(EngineStopped("x"), RuntimeError)  # legacy contract


def test_stop_race_resolves_every_future():
    """Submitters hammering across stop(): every admitted future resolves
    (the TOCTOU window between the stop-flag check and the final drain)."""
    eng = _mk_engine(max_wait_ms=0.5).start()
    ledger = FutureLedger()
    stop_submitting = threading.Event()

    def submitter(seed):
        X = _rows(400, seed=seed)
        for x in X:
            if stop_submitting.is_set():
                return
            try:
                ledger.track(eng.submit(x))
            except EngineStopped:
                return

    threads = [threading.Thread(target=submitter, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    eng.stop()
    stop_submitting.set()
    for t in threads:
        t.join()
    assert len(ledger) > 0
    ledger.assert_all_resolved(timeout=5.0)
    # a late submit stays typed
    with pytest.raises(EngineStopped):
        eng.submit(np.zeros(4, np.float32))


def test_wrong_width_row_resolves_future_not_worker():
    eng = _mk_engine().start()
    bad = eng.submit(np.zeros(7, np.float32))     # wrong width
    with pytest.raises(BadRequest):
        bad.result(5)
    # the worker never saw it and keeps serving
    good = eng.submit(np.full(4, 2.0, np.float32))
    assert good.result(5) == pytest.approx(8.0)
    eng.stop()


def test_batch_exception_reaches_every_future():
    boom = ValueError("boom")

    def bad_fn(X):
        if X.any():
            raise boom
        return _sum_fn(X)                          # warmup (zeros) passes

    eng = _mk_engine(bad_fn, max_wait_ms=50.0).start()
    futs = [eng.submit(np.full(4, 1.0 + i, np.float32)) for i in range(16)]
    eng.stop()
    excs = [f.exception(timeout=5) for f in futs]
    assert all(e is boom for e in excs)            # every future, same error


# ----------------------------------------------------------- backpressure
def test_bounded_queue_sheds_with_overloaded():
    def slow(X):
        time.sleep(0.03)
        return _sum_fn(X)

    pol = ResiliencePolicy(max_queue_depth=4)
    eng = _mk_engine(slow, policy=pol, max_batch=2).start()
    ledger = FutureLedger()
    for x in _rows(64):
        ledger.track(eng.submit(x))
    out = ledger.outcomes(timeout=20.0)
    eng.stop()
    s = eng.stats()
    assert out.get("Overloaded", 0) > 0
    assert out.get("Overloaded", 0) == s.n_shed
    assert out.get("ok", 0) + s.n_shed == 64       # nothing stranded or lost


def test_deadline_enforced_at_dequeue_and_result():
    def slow(X):
        time.sleep(0.05)
        return _sum_fn(X)

    pol = ResiliencePolicy(deadline_ms=60.0)
    eng = _mk_engine(slow, policy=pol, max_batch=1).start()
    ledger = FutureLedger()
    for x in _rows(24):
        ledger.track(eng.submit(x))                # ~1.2s of work, 60ms budget
    out = ledger.outcomes(timeout=20.0)
    eng.stop()
    s = eng.stats()
    assert out.get("DeadlineExceeded", 0) > 0
    # the dequeue triage fired too (cheaper than a wasted predict), and its
    # count never exceeds what clients observed
    assert 0 < s.n_deadline_expired <= out["DeadlineExceeded"]
    assert out.get("ok", 0) >= 1                   # early requests made it


def test_slow_predict_fault_blows_result_deadline():
    plan = FaultPlan([Fault(point="predict", action="sleep", sleep_s=0.2)])
    pol = ResiliencePolicy(deadline_ms=50.0)
    eng = _mk_engine(policy=pol, faults=plan).start()
    fut = eng.submit(np.zeros(4, np.float32))
    t0 = time.perf_counter()
    with pytest.raises(DeadlineExceeded):
        fut.result()                               # no explicit timeout needed
    assert time.perf_counter() - t0 < 0.15         # returned at the deadline
    eng.stop()
    assert plan.n_fired("predict") >= 1


# ------------------------------------------------------------- supervisor
def test_worker_crash_restart_then_serve():
    plan = FaultPlan([Fault(point="worker", at=(1,), count=1, message="die")])
    eng = _mk_engine(policy=ResiliencePolicy(restart_budget=2),
                     faults=plan).start()
    ledger = FutureLedger()
    for x in _rows(12):
        ledger.track(eng.submit(x))
        time.sleep(0.01)                            # spread across batches
    out = ledger.outcomes(timeout=20.0)
    eng.stop()
    assert out.get("WorkerCrashed", 0) >= 1         # the in-flight batch
    assert out.get("ok", 0) >= 1                    # served after restart
    assert eng.stats().n_worker_restarts == 1


def test_worker_crash_budget_exhaustion():
    plan = FaultPlan([Fault(point="worker", message="die")])  # every batch
    eng = _mk_engine(policy=ResiliencePolicy(restart_budget=1),
                     faults=plan).start()
    ledger = FutureLedger()
    with pytest.raises(EngineStopped):
        for x in _rows(200):
            ledger.track(eng.submit(x))
            time.sleep(0.005)
    out = ledger.outcomes(timeout=20.0)
    eng.stop()
    assert set(out) == {"WorkerCrashed"}            # typed, none stranded
    assert eng.stats().n_worker_restarts == 1       # budget respected


# ------------------------------------------------------ retry + fallback
def test_predict_retry_recovers_transient_fault():
    plan = FaultPlan([Fault(point="predict", at=(0,), count=1)])
    pol = ResiliencePolicy(max_retries=2, backoff_base_ms=1.0)
    eng = _mk_engine(policy=pol, faults=plan).start()
    fut = eng.submit(np.full(4, 1.0, np.float32))
    assert fut.result(5) == pytest.approx(4.0)
    eng.stop()
    s = eng.stats()
    assert s.n_predict_retries >= 1
    assert s.breaker_state["primary"] == "closed"   # retry, not a failure


def test_fallback_chain_serves_when_primary_fails():
    def bad_primary(X):
        raise RuntimeError("kernel fault")

    pol = ResiliencePolicy(breaker_threshold=1, breaker_cooldown_ms=60_000.0)
    eng = MicroBatchEngine(bad_primary, 4, policy=pol,
                           fallbacks=[("good", _sum_fn)],
                           backend_name="bad").start()
    futs = [eng.submit(x) for x in _rows(8)]
    got = np.stack([f.result(5) for f in futs])
    assert got == pytest.approx(_sum_fn(_rows(8)), abs=1e-6)
    s = eng.stats()
    eng.stop()
    assert s.breaker_state == {"bad": "open", "good": "closed"}
    assert s.active_backend == "good"
    assert s.n_fallback_batches >= 1


def test_breaker_half_open_recovers_primary():
    fail_until = 3
    calls = {"n": 0}

    def flaky(X):
        calls["n"] += 1
        if calls["n"] <= fail_until:
            raise RuntimeError("transient kernel fault")
        return _sum_fn(X)

    pol = ResiliencePolicy(breaker_threshold=1, breaker_cooldown_ms=30.0)
    eng = MicroBatchEngine(flaky, 4, policy=pol,
                           fallbacks=[("good", _sum_fn)],
                           backend_name="flaky", max_batch=1)
    eng.start()                                     # warmup fails -> trip
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline:
        eng.submit(np.ones(4, np.float32)).result(5)
        if eng.stats().active_backend == "flaky":
            break
        time.sleep(0.02)                            # let the cooldown elapse
    s = eng.stats()
    eng.stop()
    assert s.active_backend == "flaky"              # probe succeeded
    assert s.breaker_state["flaky"] == "closed"
    assert s.n_fallback_batches >= 1                # degraded service first
    assert s.n_degraded_starts == 1                 # the failed warmup shows
    assert "transient kernel fault" in s.primary_start_error


def test_all_breakers_open_still_attempts_last_resort():
    boom = RuntimeError("down")

    def bad(X):
        raise boom

    pol = ResiliencePolicy(breaker_threshold=1, breaker_cooldown_ms=60_000.0)
    eng = MicroBatchEngine(bad, 4, policy=pol, backend_name="only")
    with pytest.raises(RuntimeError):
        eng.start()                                 # no fallback: warmup raises
    eng = MicroBatchEngine(_sum_fn, 4, policy=pol, backend_name="only",
                           faults=FaultPlan([Fault(point="predict")]))
    eng.start()
    f1 = eng.submit(np.zeros(4, np.float32))        # opens the breaker
    with pytest.raises(InjectedFault):              # the real error, typed
        f1.result(5)
    f2 = eng.submit(np.zeros(4, np.float32))        # breaker open: bypassed
    with pytest.raises(InjectedFault):
        f2.result(5)
    eng.stop()


def test_gbdt_engine_fallback_parity(gbdt_model):
    """A dead primary backend falls back inside the <=1e-5 parity contract."""
    model, X = gbdt_model
    plan = FaultPlan([Fault(point="predict", backend="packed")])
    pol = ResiliencePolicy(breaker_threshold=1, breaker_cooldown_ms=60_000.0)
    eng = GBDTEngine(model, backend="packed", policy=pol, faults=plan,
                     max_wait_ms=5.0)
    assert [n for n, _ in eng._chain] == ["packed", "reference"]
    with eng:
        futs = [eng.submit(x) for x in X[:32]]
        got = np.stack([f.result(10) for f in futs])
    ref = model.predict(X[:32], backend="reference")
    assert np.abs(got - ref).max() <= 1e-5
    s = eng.stats()
    assert s.active_backend == "reference"
    assert s.breaker_state["packed"] == "open"


def test_fallback_chain_order(gbdt_model):
    model, _ = gbdt_model
    assert [n for n, _ in fallback_chain(model, "pallas")] == \
        ["packed", "reference"]
    assert [n for n, _ in fallback_chain(model, "packed")] == ["reference"]
    assert [n for n, _ in fallback_chain(model, "reference")] == []
    # unknown/custom primaries degrade through the portable backends
    assert [n for n, _ in fallback_chain(model, "custom")] == \
        ["packed", "reference"]


# ---------------------------------------------------------------- faults
def test_faultplan_deterministic_and_filtered():
    mk = lambda: FaultPlan(
        [Fault(point="predict", p=0.5, model="a"),
         Fault(point="worker", at=(2, 4))], seed=11)
    p1, p2 = mk(), mk()
    for plan in (p1, p2):
        for i in range(20):
            for point, model in (("predict", "a"), ("predict", "b"),
                                 ("worker", "")):
                try:
                    plan.fire(point, model=model)
                except InjectedFault:
                    pass
    assert p1.log == p2.log                         # same seed, same schedule
    assert all(m == "a" for pt, m, *_ in p1.log if pt == "predict")
    assert [rec[3] for rec in p1.log if rec[0] == "worker"] == [2, 4]
    with pytest.raises(ValueError, match="unknown fault point"):
        Fault(point="nope")
    with pytest.raises(ValueError, match="unknown fault action"):
        Fault(point="predict", action="explode")


def test_future_ledger_flags_stranded_future():
    import concurrent.futures

    led = FutureLedger()
    led.track(concurrent.futures.Future())          # never resolved
    with pytest.raises(AssertionError, match="1 of 1 futures stranded"):
        led.assert_all_resolved(timeout=0.1)


# ----------------------------------------------------------------- stats
def test_stats_merge_sums_resilience_counters():
    a = EngineStats(10, 2, 1.0, 10.0, 5.0, 1.0, 1.0, 2.0, n_shed=3,
                    n_deadline_expired=1, n_worker_restarts=1,
                    n_predict_retries=2, n_fallback_batches=1,
                    breaker_state={"pallas": "open"}, active_backend="packed")
    b = EngineStats(30, 3, 2.0, 15.0, 10.0, 2.0, 2.0, 4.0, n_shed=1,
                    n_deadline_expired=4, n_worker_restarts=0)
    m = EngineStats.merge([a, b])
    assert (m.n_shed, m.n_deadline_expired, m.n_worker_restarts) == (4, 5, 1)
    assert (m.n_predict_retries, m.n_fallback_batches) == (2, 1)
    assert m.breaker_state == {} and m.active_backend == ""  # per-engine facts
    assert m.n_requests == 40
    d = m.as_dict()
    assert d["n_shed"] == 4 and "breaker_state" in d


# ------------------------------------------------------------------ fleet
@pytest.fixture(scope="module")
def gbdt_model():
    r = rng(0)
    X = r.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] ** 2 > 0.7).astype(np.float32)
    model = ToadModel(task="binary", n_bins=16, n_rounds=8, max_depth=3,
                      learning_rate=0.3).fit(X, y).compress()
    return model, X


@pytest.fixture(scope="module")
def fleet_dir(tmp_path_factory, gbdt_model):
    model, X = gbdt_model
    d = tmp_path_factory.mktemp("resilience_fleet")
    model.save(str(d / "m_a.toad"))
    r = rng(1)
    y2 = (X[:, 2] > 0).astype(np.float32)
    m2 = ToadModel(task="binary", n_bins=16, n_rounds=6, max_depth=3,
                   learning_rate=0.3).fit(X, y2).compress()
    m2.save(str(d / "m_b.toad"))
    m2.save(str(d / "swap_target.toad"))
    return d


def test_fleet_swap_failure_leaves_old_version_serving(fleet_dir, gbdt_model):
    model, X = gbdt_model
    registry = ModelRegistry.from_dir(str(fleet_dir))
    # arm the admit fault *after* initial admission: the next _admit dies
    registry._faults = FaultPlan(
        [Fault(point="admit", model="m_a", message="load error mid-swap")])
    with FleetEngine(registry, max_wait_ms=1.0) as engine:
        before = engine.version("m_a")
        ref = engine.submit("m_a", X[0]).result(10)
        with pytest.raises(InjectedFault):
            engine.swap("m_a", str(fleet_dir / "swap_target.toad"))
        assert engine.version("m_a") == before       # old version serving
        got = engine.submit("m_a", X[0]).result(10)
        assert got == pytest.approx(ref, abs=1e-6)
        registry._faults = None                      # fault cleared: swap lands
        assert engine.swap(
            "m_a", str(fleet_dir / "swap_target.toad")).version == before + 1


def test_fleet_retire_threads_pruned(fleet_dir):
    registry = ModelRegistry.from_dir(str(fleet_dir))
    with FleetEngine(registry, max_wait_ms=0.5) as engine:
        engine.warm("m_b")
        for i in range(12):
            engine.swap("m_b", str(fleet_dir / "swap_target.toad"))
        engine.drain()
        engine.swap("m_b", str(fleet_dir / "swap_target.toad"))
        # pruning keeps the list bounded by *live* drains, not swap history
        assert len(engine._retire_threads) <= 2
        assert engine.stats().n_retired >= 12


def test_fleet_stats_concurrent_with_retire(fleet_dir, gbdt_model):
    _, X = gbdt_model
    registry = ModelRegistry.from_dir(str(fleet_dir))
    errors = []

    def poll_stats(engine, stop):
        try:
            while not stop.is_set():
                s = engine.stats()
                assert s.n_hot >= 0 and s.fleet.n_requests >= 0
        except Exception as e:  # pragma: no cover - the failure under test
            errors.append(e)

    with FleetEngine(registry, max_wait_ms=0.5) as engine:
        stop = threading.Event()
        t = threading.Thread(target=poll_stats, args=(engine, stop))
        t.start()
        for i in range(8):
            engine.submit("m_b", X[0]).result(10)
            engine.swap("m_b", str(fleet_dir / "swap_target.toad"))
        stop.set()
        t.join()
    assert errors == []


def test_fleet_resilience_counters_and_shed(fleet_dir, gbdt_model):
    _, X = gbdt_model
    registry = ModelRegistry.from_dir(str(fleet_dir))
    plan = FaultPlan([Fault(point="predict", action="sleep", sleep_s=0.02,
                            model="m_a")])
    pol = ResiliencePolicy(max_queue_depth=2)
    ledger = FutureLedger()
    with FleetEngine(registry, policy=pol, faults=plan, max_batch=2,
                     max_wait_ms=0.5) as engine:
        for i in range(64):
            ledger.track(engine.submit("m_a", X[i % len(X)]))
        out = ledger.outcomes(timeout=30.0)
        stats = engine.stats()
    assert stats.n_shed > 0 and out.get("Overloaded", 0) == stats.n_shed
    assert out.get("ok", 0) + stats.n_shed == 64
    assert stats.breaker_state["m_a"]                # per-model breaker view
    assert stats.active_backend["m_a"] in ("packed", "reference", "pallas")
    assert stats.as_dict()["n_shed"] == stats.n_shed


def test_fleet_stop_resolves_everything_under_crashes(fleet_dir, gbdt_model):
    """The end-to-end chaos scenario: crashes + floods, then stop() — every
    future across the fleet resolves."""
    _, X = gbdt_model
    registry = ModelRegistry.from_dir(str(fleet_dir))
    plan = FaultPlan([Fault(point="worker", model="m_a", at=(2,), count=1),
                      Fault(point="predict", model="m_b", at=(3,), count=1)])
    pol = ResiliencePolicy(max_queue_depth=16, restart_budget=2)
    ledger = FutureLedger()
    with FleetEngine(registry, policy=pol, faults=plan,
                     max_wait_ms=0.5) as engine:
        for i in range(48):
            for mid in ("m_a", "m_b"):
                try:
                    ledger.track(engine.submit(mid, X[i % len(X)]))
                except EngineStopped:
                    pass
            time.sleep(0.002)
    out = ledger.outcomes(timeout=30.0)
    allowed = {"ok", "Overloaded", "DeadlineExceeded", "WorkerCrashed",
               "EngineStopped", "InjectedFault"}
    assert set(out) <= allowed                       # typed outcomes only
    assert out.get("ok", 0) > 0
