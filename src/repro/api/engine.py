"""Model-agnostic micro-batching serving engine + the GBDT specialization.

The engine owns a request queue and a worker thread.  Clients submit single
raw-feature rows; the worker drains up to ``max_batch`` requests per step
(waiting at most ``max_wait_ms`` for stragglers after the first arrival),
pads the batch to a fixed shape bucket so the compiled predictor never
re-traces, runs one prediction, and resolves the per-request futures.

``MicroBatchEngine`` is model-agnostic: it takes any compiled
``(n, d) -> (n, C)`` function.  ``GBDTEngine`` wires it to a
:class:`~repro.api.model.ToadModel` through any registered predictor
backend — the serving path and the parity contract are the same seam.

**Resilience** (:mod:`repro.api.resilience`): with a
:class:`~repro.api.resilience.ResiliencePolicy` the engine bounds its
queue (full queue -> typed ``Overloaded`` at admission, load shedding
instead of latency collapse), enforces per-request deadlines both at
dequeue (expired requests complete with ``DeadlineExceeded`` without
wasting a predict) and inside ``submit().result()``, retries failed batch
predicts with deterministic seeded backoff, and walks a **fallback chain**
of degraded-but-correct backends (``pallas -> packed -> reference``, all
inside the <=1e-5 parity contract) guarded by per-backend circuit
breakers.  A supervisor catches worker crashes, fails the in-flight
futures with a typed ``WorkerCrashed`` error, and restarts the worker up
to ``policy.restart_budget`` times.  The invariant either way: **every**
submitted future resolves with a result or a typed exception — ``stop()``
sweeps anything still queued.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import logging
import os
import queue
import threading
import time

import numpy as np

from repro.api.resilience import (
    BadRequest,
    CircuitBreaker,
    DeadlineExceeded,
    EngineError,
    EngineStopped,
    Overloaded,
    ResiliencePolicy,
    WorkerCrashed,
)

logger = logging.getLogger("repro.api.engine")

#: backend names from most-accelerated to most-conservative; a fallback
#: chain is the suffix after the primary (see :func:`fallback_chain`)
DEGRADATION_ORDER = ("pallas", "packed", "reference")


def fallback_chain(model, primary: str) -> list:
    """``[(name, predict_fn), ...]`` for every backend less accelerated
    than ``primary`` in :data:`DEGRADATION_ORDER`.

    An unknown (custom) primary falls back through ``packed`` then
    ``reference``.  The returned functions come from ``model.predictor``,
    which caches per backend; jax traces them lazily on first use, so an
    unfaulted engine never pays for its fallbacks.
    """
    order = DEGRADATION_ORDER
    start = order.index(primary) + 1 if primary in order else 1
    return [(name, model.predictor(name)) for name in order[start:]]


class EarlyExitPredictor:
    """A ``(n, d) -> (n, C)`` adapter that realizes early exits per backend.

    Wraps a fitted :class:`~repro.api.model.ToadModel` and an
    :class:`~repro.gbdt.early_exit.EarlyExitPolicy`; the engine plugs it in
    as the primary predict function and reads its trees-evaluated counters
    into ``EngineStats.mean_trees_evaluated``.  Per backend:

    * ``pallas`` — the tile-retirement kernel
      (:func:`repro.kernels.ops.predict_packed_model_early_exit`);
    * ``packed`` — staged prefix evaluation: the packed kernel runs on
      doubling ``TREE_BLOCK``-aligned tree prefixes, rows that are
      decision-final at a checkpoint keep their prefix scores and drop out
      of later stages (row counts bucket to powers of two, so compiles are
      bounded);
    * ``reference`` — the row-level numpy evaluator
      (:func:`repro.gbdt.early_exit.predict_early_exit`).

    A never-exit policy (ε=∞) short-circuits to the model's plain
    predictor, so it is bit-identical to serving without early exit.
    Exited rows return their partial sums — same label, not the same
    score, as full evaluation.  Counter note: the engine pads batches to
    shape buckets, so padded rows count toward ``mean_trees_evaluated``
    like real ones.
    """

    def __init__(self, model, policy, backend: str | None = None):
        from repro.api.backends import resolve_backend
        from repro.core.treeorder import remaining_mass

        if model.config.task == "regression":
            raise ValueError(
                "early exit needs a discrete decision to protect; "
                "regression scores never become margin-final"
            )
        self.model = model
        self.policy = policy
        self.backend_name = resolve_backend(
            backend, compressed=model.is_compressed).name
        self._backend_arg = backend
        self.n_trees = int(model.forest.n_trees)
        self.C = int(model.forest.n_ensembles)
        self._t_eff = (self.n_trees if policy.max_trees is None
                       else min(int(policy.max_trees), self.n_trees))
        self._lock = threading.Lock()
        self._rows = 0
        self._trees = 0.0

        if policy.never_exits or self.n_trees == 0:
            self._mode = "full"
            self._full = model.predictor(backend)
            return
        self._bound = remaining_mass(model.forest)
        self._slack = policy.slack(self.C)
        if self.backend_name == "reference":
            self._mode = "reference"
            return
        if not model.is_compressed:
            model.compress()
        if self.backend_name == "pallas":
            self._mode = "kernel"
            self._init_kernel()
        else:
            self._mode = "staged"
            self._init_staged()

    # -------------------------------------------------------------- modes
    def _init_kernel(self):
        packed = self.model.packed
        self._k_packed = packed
        self._k_bound = self._bound
        if self._t_eff < self.n_trees:  # max_trees cap: serve the prefix
            self._k_packed = dataclasses.replace(
                packed,
                words=np.asarray(packed.words)[: self._t_eff],
                leaf_ref=np.asarray(packed.leaf_ref)[: self._t_eff],
            )
            self._k_bound = self._bound[: self._t_eff + 1]

    def _init_staged(self):
        import jax.numpy as jnp

        from repro.kernels.predict import TREE_BLOCK

        packed = self.model.packed
        T = self._t_eff
        # checkpoints double from one tree block; every edge is a multiple
        # of C (tree_block is), so a prefix kernel call assigns the right
        # class columns
        tb = -(-TREE_BLOCK // self.C) * self.C
        ks: list[int] = []
        k = tb
        while k < T:
            ks.append(k)
            k *= 2
        edges = [0] + ks + [T]
        self._edges = list(zip(edges[:-1], edges[1:]))
        words = np.asarray(packed.words)
        lref = np.asarray(packed.leaf_ref)
        zero_base = jnp.zeros_like(jnp.asarray(packed.base_score))
        self._stage_arrays = [
            (jnp.asarray(words[a:b]), jnp.asarray(lref[a:b]),
             jnp.asarray(packed.base_score) if a == 0 else zero_base)
            for a, b in self._edges
        ]
        self._tables = tuple(
            jnp.asarray(getattr(packed, f))
            for f in ("leaf_values", "thr_table", "thr_offsets",
                      "used_features")
        )

    def _run_stage(self, si: int, xa: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from repro.kernels.ops import _interp
        from repro.kernels.predict import packed_predict

        packed = self.model.packed
        m = xa.shape[0]
        mb = 1 << (m - 1).bit_length()  # pow-2 bucket bounds retraces
        if mb != m:
            xa = np.concatenate(
                [xa, np.zeros((mb - m, xa.shape[1]), np.float32)])
        words, lref, base = self._stage_arrays[si]
        leaf_values, thr_table, thr_offsets, used_features = self._tables
        out = packed_predict(
            jnp.asarray(xa), words, lref, leaf_values, thr_table,
            thr_offsets, used_features, base,
            max_depth=packed.max_depth, tidx_bits=packed.tidx_bits,
            n_ensembles=self.C, interpret=_interp(),
        )
        return np.asarray(out)[:m]

    def _staged(self, x: np.ndarray):
        from repro.gbdt.early_exit import decision_final_mask

        n = x.shape[0]
        partial = np.zeros((n, self.C), np.float32)
        trees = np.full(n, self._t_eff, np.int32)
        active = np.arange(n)
        for si, (a, b) in enumerate(self._edges):
            vals = self._run_stage(si, x[active])
            if a == 0:
                partial[active] = vals
            else:
                partial[active] += vals
            if b >= self._t_eff:
                break
            if b >= self.policy.min_trees:
                fin = np.asarray(decision_final_mask(
                    partial[active].astype(np.float64), self._bound[b],
                    self._slack, self.policy.guard))
                trees[active[fin]] = b
                active = active[~fin]
            if active.size == 0:
                break
        return partial, trees

    # --------------------------------------------------------------- call
    def __call__(self, rows) -> np.ndarray:
        x = np.asarray(rows, np.float32)
        n = x.shape[0]
        if self._mode == "full":
            out = np.asarray(self._full(x))
            self._account(n, float(n * self.n_trees))
            return out
        if self._mode == "kernel":
            from repro.kernels.ops import predict_packed_model_early_exit

            scores, trees, _ = predict_packed_model_early_exit(
                self._k_packed, x, self._k_bound, self._slack,
                guard=self.policy.guard, min_trees=self.policy.min_trees)
            scores = np.asarray(scores)
        elif self._mode == "reference":
            from repro.gbdt.early_exit import predict_early_exit
            from repro.kernels.predict import TREE_BLOCK

            res = predict_early_exit(
                self.model.forest, x, self.policy, bound=self._bound,
                check_every=TREE_BLOCK)
            scores, trees = res.scores, res.trees_evaluated
        else:
            scores, trees = self._staged(x)
        self._account(n, float(np.sum(trees)))
        return scores

    @property
    def mode(self) -> str:
        """The serving path in use: full | reference | kernel | staged."""
        return self._mode

    # -------------------------------------------------------------- stats
    def _account(self, n: int, trees_total: float) -> None:
        with self._lock:
            self._rows += n
            self._trees += trees_total

    def reset(self) -> None:
        """Zero the counters (the engine calls this after warmup)."""
        with self._lock:
            self._rows = 0
            self._trees = 0.0

    def mean_trees_evaluated(self) -> float:
        with self._lock:
            return self._trees / self._rows if self._rows else 0.0

    def rows_counted(self) -> int:
        """Rows accounted so far (the weight for fleet-wide merging)."""
        with self._lock:
            return self._rows


class _EngineFuture(concurrent.futures.Future):
    """A Future that enforces the request deadline inside ``result()``."""

    _deadline_t: float | None = None

    def result(self, timeout=None):
        if self._deadline_t is not None:
            remaining = self._deadline_t - time.perf_counter()
            if timeout is None or remaining < timeout:
                try:
                    return super().result(timeout=max(remaining, 0.0))
                except concurrent.futures.TimeoutError:
                    raise DeadlineExceeded(
                        "request deadline exceeded while waiting for the "
                        "result"
                    ) from None
        return super().result(timeout)


@dataclasses.dataclass
class EngineStats:
    n_requests: int
    n_batches: int
    wall_s: float
    req_per_s: float
    mean_batch: float
    latency_mean_ms: float
    latency_p50_ms: float
    latency_p95_ms: float
    #: requests waiting in the queue at the moment stats() was taken
    queue_depth: int = 0
    #: per shape-bucket occupancy: {bucket_size: {"batches": n, "mean_fill":
    #: real_rows / (n * bucket_size)}} — shows whether cross-tenant batching
    #: actually fills the padded buckets or mostly pads
    batch_occupancy: dict = dataclasses.field(default_factory=dict)
    #: admissions rejected with Overloaded (bounded queue full)
    n_shed: int = 0
    #: requests that expired in the queue (DeadlineExceeded at dequeue)
    n_deadline_expired: int = 0
    #: worker restarts after a crash (supervisor)
    n_worker_restarts: int = 0
    #: batch predict retries (before backend fallback / failure)
    n_predict_retries: int = 0
    #: batches served by a non-primary backend (degraded but correct)
    n_fallback_batches: int = 0
    #: per-backend circuit-breaker state: {backend: closed|open|half_open}
    breaker_state: dict = dataclasses.field(default_factory=dict)
    #: the backend that served the most recent batch
    active_backend: str = ""
    #: mean trees evaluated per row under an early-exit policy (0.0 when
    #: early exit is off; includes batch-padding rows)
    mean_trees_evaluated: float = 0.0
    #: rows the early-exit adapter accounted (the merge weight; counts
    #: direct ``predict()`` traffic that never enters the request queue)
    n_early_exit_rows: int = 0
    #: starts whose primary backend failed its warmup, so the engine
    #: began on a fallback (degraded start)
    n_degraded_starts: int = 0
    #: the error that failed the primary's warmup ("" when it did not)
    primary_start_error: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def merge(parts: "list[EngineStats]") -> "EngineStats":
        """Aggregate across engines (fleet-wide view).

        Counts and occupancy sum exactly; wall clock is the max (engines run
        concurrently); latency mean and percentiles are request-weighted
        averages of the per-engine values — an approximation that is exact
        for the mean and a reasonable operational summary for p50/p95.
        Per-backend breaker state and the active backend are per-engine
        facts and stay empty on the merged view.
        """
        parts = [p for p in parts if p is not None]
        if not parts:
            return EngineStats(0, 0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0)
        n = sum(p.n_requests for p in parts)
        ee_parts = [p for p in parts if p.n_early_exit_rows > 0]
        ee_n = sum(p.n_early_exit_rows for p in ee_parts)
        wall = max(p.wall_s for p in parts)
        wavg = (
            lambda f: sum(f(p) * p.n_requests for p in parts) / n if n else 0.0
        )
        occupancy: dict = {}
        for p in parts:
            for bucket, o in p.batch_occupancy.items():
                cur = occupancy.setdefault(bucket, {"batches": 0, "mean_fill": 0.0})
                tot = cur["batches"] + o["batches"]
                if tot:
                    cur["mean_fill"] = (
                        cur["mean_fill"] * cur["batches"]
                        + o["mean_fill"] * o["batches"]
                    ) / tot
                cur["batches"] = tot
        return EngineStats(
            n_requests=n,
            n_batches=sum(p.n_batches for p in parts),
            wall_s=wall,
            req_per_s=n / max(wall, 1e-9),
            mean_batch=wavg(lambda p: p.mean_batch),
            latency_mean_ms=wavg(lambda p: p.latency_mean_ms),
            latency_p50_ms=wavg(lambda p: p.latency_p50_ms),
            latency_p95_ms=wavg(lambda p: p.latency_p95_ms),
            queue_depth=sum(p.queue_depth for p in parts),
            batch_occupancy=occupancy,
            n_shed=sum(p.n_shed for p in parts),
            n_deadline_expired=sum(p.n_deadline_expired for p in parts),
            n_worker_restarts=sum(p.n_worker_restarts for p in parts),
            n_predict_retries=sum(p.n_predict_retries for p in parts),
            n_fallback_batches=sum(p.n_fallback_batches for p in parts),
            # row-weighted over the engines actually running early exit
            mean_trees_evaluated=(
                sum(p.mean_trees_evaluated * p.n_early_exit_rows
                    for p in ee_parts)
                / ee_n if ee_n else 0.0
            ),
            n_early_exit_rows=ee_n,
            n_degraded_starts=sum(p.n_degraded_starts for p in parts),
        )


class MicroBatchEngine:
    """Batches single-row requests through one compiled predict function."""

    def __init__(
        self,
        predict_fn,
        n_features: int,
        *,
        max_batch: int = 256,
        max_wait_ms: float = 2.0,
        policy: ResiliencePolicy | None = None,
        fallbacks=(),
        backend_name: str = "primary",
        faults=None,
        fault_tag: str = "",
        early_exit: EarlyExitPredictor | None = None,
    ):
        self._predict = predict_fn
        #: the EarlyExitPredictor serving as predict_fn, if any — read for
        #: EngineStats.mean_trees_evaluated and reset after warmup
        self._early_exit = early_exit
        self.n_features = n_features
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        self.policy = policy if policy is not None else ResiliencePolicy()
        self._deadline_s = self.policy.deadline_ms / 1e3
        self._chain: list = [(backend_name, predict_fn)] + list(fallbacks)
        self._breakers = [
            CircuitBreaker(self.policy.breaker_threshold,
                           self.policy.breaker_cooldown_ms / 1e3)
            for _ in self._chain
        ]
        self._faults = faults
        self._fault_tag = fault_tag
        self._queue: queue.Queue = queue.Queue(
            maxsize=max(0, self.policy.max_queue_depth)
        )
        self._worker: threading.Thread | None = None
        self._stop = threading.Event()
        #: serializes submit()'s stopped-check-then-enqueue against stop()'s
        #: flag-set-then-drain, closing the late-enqueue TOCTOU window
        self._admission_lock = threading.Lock()
        self._stopping = False
        self._crashed = False
        self._inflight: list = []
        self._latencies: list[float] = []
        self._batch_sizes: list[int] = []
        self._bucket_hits: dict[int, list[int]] = {}  # bucket -> [batches, rows]
        self._t_start = 0.0
        self._t_busy_end = 0.0
        self._n_shed = 0
        self._n_deadline = 0
        self._n_restarts = 0
        self._n_crashes = 0
        self._n_retries = 0
        self._n_fallback = 0
        self._n_degraded_starts = 0
        self._start_error = ""
        self._active_idx = 0
        self._backoff_rng = np.random.default_rng(self.policy.seed)

    # ---------------------------------------------------------------- client
    def submit(self, x_row) -> concurrent.futures.Future:
        """Enqueue one (d,) raw-feature request; resolves to a (C,) score.

        Typed failures: :class:`EngineStopped` when the engine is not
        started / stopped / crashed out of its restart budget;
        :class:`Overloaded` when the bounded queue is full; a returned
        future carrying :class:`BadRequest` when the row cannot be shaped
        to the model's feature width.
        """
        t_in = time.perf_counter()
        fut = _EngineFuture()
        if self._deadline_s:
            fut._deadline_t = t_in + self._deadline_s
        try:
            row = np.asarray(x_row, dtype=np.float32).reshape(self.n_features)
        except Exception as exc:
            # resolve, don't raise: the malformed row must never reach the
            # worker (np.stack would kill the whole batch) and async
            # clients expect the error on the future they hold
            fut.set_exception(BadRequest(
                f"cannot shape request of size {np.asarray(x_row).size} to "
                f"({self.n_features},): {exc}"
            ))
            return fut
        with self._admission_lock:
            if self._worker is None or self._stopping:
                raise EngineStopped(
                    "engine not started" if not self._crashed else
                    "engine worker crashed out of its restart budget"
                )
            try:
                self._queue.put_nowait((row, t_in, fut))
            except queue.Full:
                self._n_shed += 1
                fut.set_exception(Overloaded(
                    f"queue full ({self.policy.max_queue_depth} deep); "
                    f"request shed at admission"
                ))
        return fut

    def predict(self, X) -> np.ndarray:
        """Direct batched call through the same compiled path (no queue)."""
        return np.asarray(self._predict(np.asarray(X, dtype=np.float32)))

    # ---------------------------------------------------------------- worker
    def start(self) -> "MicroBatchEngine":
        if self._worker is not None:
            return self
        self._stop.clear()
        self._stopping = False
        self._crashed = False
        self._latencies.clear()
        self._batch_sizes.clear()
        self._bucket_hits.clear()
        self._n_shed = self._n_deadline = 0
        self._n_restarts = self._n_crashes = 0
        self._n_retries = self._n_fallback = 0
        self._active_idx = 0
        # warm the compiled predictor at every bucket shape so steady-state
        # latency never pays a trace (and the stats clock starts after it)
        self._start_error = ""
        try:
            for b in self._buckets():
                self._predict(np.zeros((b, self.n_features), np.float32))
        except Exception as exc:
            if len(self._chain) == 1:
                raise
            # a broken primary with fallbacks available is a degraded
            # start, not a failed one: trip its breaker, record why (stats
            # shows it) and serve on
            self._breakers[0].trip()
            self._n_degraded_starts += 1
            self._start_error = repr(exc)
            logger.warning(
                "primary backend %r failed its warmup; serving on %r: %r",
                self._chain[0][0], self._chain[1][0], exc)
        if self._early_exit is not None:
            self._early_exit.reset()  # warmup rows must not skew the mean
        self._t_start = time.perf_counter()
        self._worker = threading.Thread(
            target=self._supervise, name="gbdt-engine", daemon=True
        )
        self._worker.start()
        return self

    def stop(self) -> "MicroBatchEngine":
        """Stop the worker after draining the queue.

        Guaranteed post-condition: every future ever returned by
        ``submit()`` is resolved — drained requests with results, anything
        left behind by a crashed worker with a typed error — and late
        ``submit()`` calls raise :class:`EngineStopped` instead of
        enqueueing into a queue no worker will drain.
        """
        if self._worker is None:
            return self
        with self._admission_lock:
            self._stopping = True  # no admissions from here on
        self._stop.set()
        self._worker.join()
        self._worker = None
        # the worker drains the queue before exiting; anything still queued
        # means it crashed out — resolve those futures, never strand them
        self._fail_pending(EngineStopped("engine stopped"))
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def _fail_pending(self, err: Exception) -> int:
        n = 0
        while True:
            try:
                _, _, fut = self._queue.get_nowait()
            except queue.Empty:
                return n
            if not fut.done():
                fut.set_exception(err)
                n += 1

    def _buckets(self):
        b, out = 1, []
        while b < self.max_batch:
            out.append(b)
            b *= 2
        out.append(self.max_batch)
        return out

    def _bucket(self, n: int) -> int:
        for b in self._buckets():
            if n <= b:
                return b
        return self.max_batch

    def _supervise(self):
        """Run the worker loop, restarting it after crashes.

        A crash (an exception escaping :meth:`_run`, e.g. an injected
        worker fault) fails the in-flight futures with a typed
        :class:`WorkerCrashed` and restarts the loop, up to
        ``policy.restart_budget`` restarts; past the budget the engine
        fails every queued future and refuses new admissions.
        """
        while True:
            try:
                self._run()
                return  # clean stop
            except Exception as exc:  # worker crash
                err = WorkerCrashed(f"engine worker crashed: {exc!r}")
                err.__cause__ = exc
                inflight, self._inflight = self._inflight, []
                for _, _, fut in inflight:
                    if not fut.done():
                        fut.set_exception(err)
                self._n_crashes += 1
                if (
                    self._n_crashes > self.policy.restart_budget
                    or self._stop.is_set()
                ):
                    with self._admission_lock:
                        self._crashed = True
                        self._stopping = True
                    self._fail_pending(err)
                    return
                self._n_restarts += 1

    def _run(self):
        while not (self._stop.is_set() and self._queue.empty()):
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            batch = [first]
            wait_until = time.perf_counter() + self.max_wait_s
            while len(batch) < self.max_batch:
                remaining = wait_until - time.perf_counter()
                if remaining <= 0 and self._queue.empty():
                    break
                try:
                    batch.append(self._queue.get(timeout=max(remaining, 0.0)))
                except queue.Empty:
                    break
            self._inflight = batch
            if self._faults is not None:
                # the injected-worker-crash point: raises with the batch in
                # hand, exercising the supervisor's in-flight failing
                self._faults.fire("worker", model=self._fault_tag)
            if self._deadline_s:
                now = time.perf_counter()
                live = []
                for item in batch:
                    if now - item[1] > self._deadline_s:
                        self._n_deadline += 1
                        if not item[2].done():
                            item[2].set_exception(DeadlineExceeded(
                                "request expired in the queue before a "
                                "prediction was attempted"
                            ))
                    else:
                        live.append(item)
                batch = live
                self._inflight = live
                if not batch:
                    continue
            rows = np.stack([b[0] for b in batch])
            n = rows.shape[0]
            padded = self._bucket(n)
            if padded != n:
                rows = np.concatenate(
                    [rows, np.zeros((padded - n, self.n_features), np.float32)]
                )
            try:
                scores = self._predict_batch(rows)[:n]
            except Exception as exc:
                # never strand clients: fail this batch's futures and keep
                # the worker alive for the rest of the queue
                for _, _, fut in batch:
                    if not fut.done():
                        fut.set_exception(exc)
                self._inflight = []
                continue
            done = time.perf_counter()
            self._batch_sizes.append(n)
            hit = self._bucket_hits.setdefault(padded, [0, 0])
            hit[0] += 1
            hit[1] += n
            for (_, t_in, fut), s in zip(batch, scores):
                self._latencies.append(done - t_in)
                if not fut.done():
                    fut.set_result(s)
            self._inflight = []
            self._t_busy_end = done

    def _predict_batch(self, rows: np.ndarray) -> np.ndarray:
        """One batch through the backend chain: retries with deterministic
        backoff on the active backend, then on to the next breaker-allowed
        fallback.  A success closes the backend's breaker; exhausting a
        backend's retries records one consecutive-failure toward opening
        it."""
        last_exc: Exception | None = None

        def attempt(idx: int) -> np.ndarray | None:
            nonlocal last_exc
            name, fn = self._chain[idx]
            for retry in range(self.policy.max_retries + 1):
                try:
                    if self._faults is not None:
                        self._faults.fire(
                            "predict", model=self._fault_tag, backend=name
                        )
                    out = np.asarray(fn(rows))
                except Exception as exc:
                    last_exc = exc
                    if retry < self.policy.max_retries:
                        self._n_retries += 1
                        time.sleep(self._backoff_s(retry))
                    continue
                self._breakers[idx].record_success()
                self._active_idx = idx
                if idx > 0:
                    self._n_fallback += 1
                return out
            self._breakers[idx].record_failure()
            return None

        attempted = False
        for idx in range(len(self._chain)):
            if not self._breakers[idx].allow():
                continue
            attempted = True
            out = attempt(idx)
            if out is not None:
                return out
        if not attempted:
            # every breaker is open mid-cooldown; degraded-but-serving
            # beats down, so bypass the breaker on the most-conservative
            # backend rather than failing the batch unattempted
            out = attempt(len(self._chain) - 1)
            if out is not None:
                return out
        raise last_exc if last_exc is not None else EngineError(
            "no backend available (all circuit breakers open)"
        )

    def _backoff_s(self, retry: int) -> float:
        p = self.policy
        step = p.backoff_base_ms * p.backoff_mult**retry
        jitter = 1.0 + p.backoff_jitter * float(self._backoff_rng.random())
        return (step * jitter) / 1e3

    # ----------------------------------------------------------------- stats
    def stats(self) -> EngineStats:
        lat = np.asarray(self._latencies, dtype=np.float64)
        n = int(lat.size)
        wall = max(self._t_busy_end - self._t_start, 1e-9)
        return EngineStats(
            n_requests=n,
            n_batches=len(self._batch_sizes),
            wall_s=wall,
            req_per_s=n / wall,
            mean_batch=float(np.mean(self._batch_sizes)) if self._batch_sizes else 0.0,
            latency_mean_ms=float(lat.mean() * 1e3) if n else 0.0,
            latency_p50_ms=float(np.percentile(lat, 50) * 1e3) if n else 0.0,
            latency_p95_ms=float(np.percentile(lat, 95) * 1e3) if n else 0.0,
            queue_depth=self._queue.qsize(),
            batch_occupancy={
                bucket: {
                    "batches": batches,
                    "mean_fill": rows / (batches * bucket),
                }
                for bucket, (batches, rows) in sorted(self._bucket_hits.items())
            },
            n_shed=self._n_shed,
            n_deadline_expired=self._n_deadline,
            n_worker_restarts=self._n_restarts,
            n_predict_retries=self._n_retries,
            n_fallback_batches=self._n_fallback,
            breaker_state={
                name: br.state
                for (name, _), br in zip(self._chain, self._breakers)
            },
            active_backend=self._chain[self._active_idx][0],
            mean_trees_evaluated=(
                self._early_exit.mean_trees_evaluated()
                if self._early_exit is not None else 0.0
            ),
            n_early_exit_rows=(
                self._early_exit.rows_counted()
                if self._early_exit is not None else 0
            ),
            n_degraded_starts=self._n_degraded_starts,
            primary_start_error=self._start_error,
        )


class GBDTEngine(MicroBatchEngine):
    """A MicroBatchEngine serving a ToadModel through a named backend.

    ``model`` may also be a path to a prebuilt ``.toad`` artifact — the
    deployment flow: compile/compress once, ship the artifact, serve it
    without retraining.

    With a :class:`~repro.api.resilience.ResiliencePolicy` whose
    ``fallback`` is set, the engine builds the degraded-backend chain from
    the backend registry (:func:`fallback_chain`): a ``pallas`` engine
    falls back to ``packed`` then ``reference`` when its breaker opens —
    slower, but inside the <=1e-5 parity contract.

    ``early_exit`` takes an :class:`~repro.gbdt.early_exit
    .EarlyExitPolicy`: the primary predict function becomes an
    :class:`EarlyExitPredictor` (same labels, partial scores on exited
    rows) and ``stats().mean_trees_evaluated`` reports the per-row average
    prefix length.
    """

    def __init__(
        self,
        model,
        *,
        backend: str | None = None,
        max_batch: int = 256,
        max_wait_ms: float = 2.0,
        policy: ResiliencePolicy | None = None,
        faults=None,
        fault_tag: str = "",
        early_exit=None,
    ):
        if isinstance(model, (str, os.PathLike)):
            from repro.api.artifact import load_checked

            model = load_checked(model).model
        from repro.api.backends import resolve_backend

        ee_adapter = None
        if early_exit is not None:
            ee_adapter = EarlyExitPredictor(model, early_exit,
                                            backend=backend)
            fn = ee_adapter
        else:
            fn = model.predictor(backend)
        primary = resolve_backend(backend, compressed=model.is_compressed).name
        # fallbacks stay full-evaluation predictors: degraded-but-correct,
        # they just stop saving trees
        fallbacks = (
            fallback_chain(model, primary)
            if policy is not None and policy.fallback
            else ()
        )
        d = int(model.forest.n_features)
        super().__init__(
            fn,
            d,
            max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            policy=policy,
            fallbacks=fallbacks,
            backend_name=primary,
            faults=faults,
            fault_tag=fault_tag,
            early_exit=ee_adapter,
        )
        self.model = model
        self.backend = backend or "auto"
        self.early_exit = early_exit
