"""``ToadModel`` — the one-object estimator facade over the whole pipeline.

The paper's lifecycle is train -> compress (ToaD stream, Sec. 3.2) ->
deploy; this class is that lifecycle as an object::

    model = ToadModel(task="binary", n_rounds=64, max_depth=3,
                      toad_penalty_feature=4.0, toad_penalty_threshold=1.0)
    model.fit(X_train, y_train).compress()
    scores = model.predict(X_test)                  # auto backend
    scores = model.predict(X_test, backend="packed")
    model.save("model.toad.npz");  ToadModel.load("model.toad.npz")

``predict`` returns the raw (n, C) ensemble margins — exactly what the
deployed C implementation on an MCU computes, and bit-for-bit what
``repro.gbdt.predict_raw`` returns.  ``predict_proba`` / ``predict_label``
apply the task's link function on top.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.backends import PredictorBackend, resolve_backend
from repro.core import (
    compression_summary,
    reuse_factor,
)
from repro.core.layout import EncodedModel
from repro.core.pipeline import (
    CompressionReport,
    CompressionSpec,
    run_pipeline,
    search_budget,
)
from repro.gbdt import GBDTConfig, apply_bins, fit_bins, make_loss
from repro.gbdt.forest import Forest

_FOREST_FIELDS = (
    "feature",
    "thr_bin",
    "is_split",
    "leaf_ref",
    "leaf_values",
    "n_leaf_values",
    "n_trees",
    "edges",
    "base_score",
)


class NotFittedError(RuntimeError):
    pass


class ToadModel:
    """Estimator facade: fit / compress / predict / save / memory_report."""

    def __init__(
        self,
        task: str = "regression",
        n_classes: int = 0,
        n_bins: int = 64,
        config: GBDTConfig | None = None,
        **config_kwargs,
    ):
        if config is None:
            config = GBDTConfig(task=task, n_classes=n_classes, **config_kwargs)
        elif config_kwargs:
            config = dataclasses.replace(config, **config_kwargs)
        self.config = config
        self.n_bins = n_bins
        self.forest: Forest | None = None
        self.history: dict | None = None
        self.aux: dict | None = None
        self.encoded: EncodedModel | None = None
        self.decoded = None
        self.packed = None
        self.spec: CompressionSpec | None = None
        self.compression_report: CompressionReport | None = None
        self.artifact_meta: dict | None = None
        #: optional EarlyExitPolicy serialized into .toad/.toadpack
        #: manifests; a serving preference, not fit state, so refits and
        #: recompression leave it in place
        self.early_exit_policy = None
        self._forest_exact: Forest | None = None
        self._loss = make_loss(config.task, config.n_classes)
        self._predict_fns: dict[str, object] = {}

    @classmethod
    def from_forest(
        cls, forest: Forest, config: GBDTConfig | None = None, n_bins: int | None = None
    ) -> "ToadModel":
        """Wrap an already-trained :class:`Forest` (e.g. from the distributed
        trainer or a hand-built ensemble) in the estimator facade."""
        if config is None:
            task = "multiclass" if forest.n_ensembles > 1 else "regression"
            config = GBDTConfig(task=task, n_classes=forest.n_ensembles)
        model = cls(config=config, n_bins=n_bins or forest.n_bins)
        model.forest = forest
        return model

    # ------------------------------------------------------------- lifecycle
    @property
    def is_fitted(self) -> bool:
        return self.forest is not None

    @property
    def is_compressed(self) -> bool:
        return self.packed is not None

    def _require_fitted(self):
        if not self.is_fitted:
            raise NotFittedError("call fit() (or load()) before this operation")

    def fit(self, X, y) -> "ToadModel":
        """Bin ``X``, train the ToaD-regularized GBDT, keep the history.

        Host spans, on the profiler's clock: ``toad.fit`` around
        ``toad.fit.inputs`` (binning, staging the arrays on the device) and
        ``toad.fit.dispatch`` (the trainer's call, which returns before the
        device finishes).
        """
        from repro.gbdt import train_jit

        with TraceAnnotation("toad.fit"):
            with TraceAnnotation("toad.fit.inputs"):
                X = np.asarray(X, dtype=np.float32)
                y = np.asarray(y, dtype=np.float32)
                edges = jnp.asarray(fit_bins(X, self.n_bins))
                bins = apply_bins(jnp.asarray(X), edges)
                y = jnp.asarray(y)
            with TraceAnnotation("toad.fit.dispatch"):
                self.forest, self.history, self.aux = train_jit(
                    self.config, bins, y, edges
                )
        self._reset_artifacts()  # fitted state changed
        return self

    def fit_binned(self, bins, y, edges) -> "ToadModel":
        """Train from pre-binned features + edges (skips the binning pass).

        The benchmark drivers bin a dataset once and train many models on
        it; this entry point keeps that efficiency while everything
        downstream (compress / predict / report) goes through the facade.

        Host spans as in :meth:`fit`; ``toad.fit.inputs`` only stages the
        arrays on the device.
        """
        from repro.gbdt import train_jit

        with TraceAnnotation("toad.fit"):
            with TraceAnnotation("toad.fit.inputs"):
                args = (jnp.asarray(bins), jnp.asarray(np.asarray(y, np.float32)),
                        jnp.asarray(edges))
            with TraceAnnotation("toad.fit.dispatch"):
                self.forest, self.history, self.aux = train_jit(self.config, *args)
        self._reset_artifacts()
        return self

    def _reset_artifacts(self):
        """Drop compiled predictors and compression artifacts (state changed)."""
        self.encoded = self.decoded = self.packed = None
        self.spec = self.compression_report = self.artifact_meta = None
        self._forest_exact = None
        self._predict_fns.clear()

    def compress(
        self,
        spec: CompressionSpec | dict | str | None = None,
        budget_bytes: float | None = None,
        probe=None,
        max_pred_delta: float | None = None,
    ) -> "ToadModel":
        """Run the staged compression pipeline and keep its artifacts.

        With no arguments this is the historical lossless chain (encode ->
        bit stream, decode -> dense arrays, to_packed -> uint32 node words),
        byte-identical to prior releases.  ``spec`` selects/orders stages
        declaratively (a :class:`CompressionSpec`, its dict, or its JSON);
        ``budget_bytes`` instead walks the budget ladder — exact -> fp16
        leaves -> leaf codebooks interleaved with shared-threshold-codebook
        rungs — and keeps the first plan whose encoded stream fits.
        ``max_pred_delta`` (budget search only) adds an accuracy floor:
        rungs whose probe-set prediction drift exceeds it are rejected even
        when their bytes fit.  The resulting :class:`CompressionReport`
        lands on ``self.compression_report``; a lossy plan replaces
        ``self.forest`` with the transformed forest so *every* backend
        (reference included) executes the deployed model.  Recompression
        always restarts from the exact forest.  Returns self for chaining.
        """
        self._require_fitted()
        if spec is not None and budget_bytes is not None:
            raise ValueError("pass either spec= or budget_bytes=, not both")
        if max_pred_delta is not None and budget_bytes is None:
            raise ValueError(
                "max_pred_delta gates the budget ladder; pass it together "
                "with budget_bytes"
            )
        if isinstance(spec, str):
            spec = CompressionSpec.from_json(spec)
        elif isinstance(spec, dict):
            spec = CompressionSpec.from_dict(spec)
        base = self.forest if self._forest_exact is None else self._forest_exact
        if budget_bytes is not None:
            res = search_budget(
                base, budget_bytes, probe=probe, max_pred_delta=max_pred_delta
            )
        else:
            res = run_pipeline(base, spec, probe=probe)
        if res.packed is None:
            raise ValueError(
                "spec must include the 'encode' and 'pack' stages to produce "
                f"a deployable artifact (got stages={res.report.spec.stages})"
            )
        self._forest_exact = base
        self.forest = res.forest
        self.encoded, self.decoded, self.packed = res.encoded, res.decoded, res.packed
        self.spec = res.report.spec
        self.compression_report = res.report
        self._predict_fns.clear()
        return self

    @property
    def forest_exact(self) -> Forest | None:
        """The untransformed trained forest (before any lossy stage)."""
        return self._forest_exact if self._forest_exact is not None else self.forest

    # ------------------------------------------------------------ prediction
    def predictor(self, backend: str | PredictorBackend | None = None):
        """The compiled ``(n, d) -> (n, C)`` function for a backend.

        Backends that execute the packed artifact trigger ``compress()``
        implicitly on first use.
        """
        self._require_fitted()
        if isinstance(backend, PredictorBackend):
            b = backend
        else:
            b = resolve_backend(backend, compressed=self.is_compressed)
        if b.requires_compressed and not self.is_compressed:
            self.compress()
        fn = self._predict_fns.get(b.name)
        if fn is None:
            fn = b.build(self)
            self._predict_fns[b.name] = fn
        return fn

    def predict(self, X, backend: str | None = None) -> np.ndarray:
        """(n, d) raw floats -> (n, C) raw ensemble scores (margins)."""
        x = jnp.asarray(np.asarray(X, dtype=np.float32))
        return np.asarray(self.predictor(backend)(x))

    def predict_proba(self, X, backend: str | None = None) -> np.ndarray:
        """(n, d) -> (n, n_classes) probabilities (classification tasks)."""
        scores = self.predict(X, backend=backend)
        if self.config.task == "binary":
            p = 1.0 / (1.0 + np.exp(-scores[:, 0]))
            return np.stack([1.0 - p, p], axis=1)
        if self.config.task == "multiclass":
            z = scores - scores.max(axis=1, keepdims=True)
            e = np.exp(z)
            return e / e.sum(axis=1, keepdims=True)
        raise ValueError("predict_proba is undefined for regression")

    def predict_label(self, X, backend: str | None = None) -> np.ndarray:
        """(n, d) -> (n,) predicted value / class id."""
        scores = self.predict(X, backend=backend)
        if self.config.task == "binary":
            return (scores[:, 0] > 0).astype(np.int32)
        if self.config.task == "multiclass":
            return np.argmax(scores, axis=1).astype(np.int32)
        return scores[:, 0]

    def score(self, X, y, backend: str | None = None) -> float:
        """Task metric (R² / accuracy) on raw features."""
        scores = self.predict(X, backend=backend)
        return float(
            self._loss.metric(jnp.asarray(np.asarray(y, np.float32)), jnp.asarray(scores))
        )

    # -------------------------------------------------------------- analysis
    def memory_report(self) -> dict:
        """All layout sizes + reuse factor + the encoded stream length.

        Works before ``compress()``: the stream length then falls back to
        the ``toad_bits_host`` estimate (the encoder run on the fly) and is
        labeled ``encoded_stream_basis="estimated"`` instead of
        ``"encoded"``; the two agree exactly for lossless specs.
        """
        self._require_fitted()
        report = compression_summary(self.forest)
        report["reuse_factor"] = reuse_factor(self.forest)
        if self.encoded is not None:
            report["encoded_stream_bytes"] = self.encoded.n_bytes
            report["encoded_stream_bits"] = self.encoded.n_bits
            report["encoded_stream_basis"] = "encoded"
        else:
            # compression_summary already ran the encoder for toad_bytes
            report["encoded_stream_bytes"] = report["toad_bytes"]
            report["encoded_stream_bits"] = int(round(report["toad_bytes"] * 8))
            report["encoded_stream_basis"] = "estimated"
        if self.compression_report is not None:
            report["compression_spec"] = self.compression_report.spec.name
            report["max_abs_pred_delta"] = self.compression_report.max_abs_pred_delta
        if self.aux is not None and "toad_bytes" in self.aux:
            report["trainer_accounted_bytes"] = float(np.asarray(self.aux["toad_bytes"]))
        return report

    # ------------------------------------------------------------ persistence
    def verify(self) -> list:
        """Structurally verify the fitted model (``repro.analysis.verify``).

        Returns the list of :class:`~repro.analysis.Diagnostic` findings —
        empty for a well-formed model.  ``save()`` runs the same checks and
        refuses on any error-severity finding.
        """
        from repro.analysis.verify import verify_model

        self._require_fitted()
        return verify_model(self)

    def save(self, path: str, verify: bool = True) -> str:
        """Persist as a versioned .toad artifact (see ``repro.api.artifact``).

        The bundle carries the format version, compression spec, encoded
        stream, manifest and eval fingerprint; the path is written verbatim
        (``model.toad`` stays ``model.toad``).  With ``verify=True``
        (default) the bundle is structurally verified post-encode and the
        save refuses on any error-severity finding.
        """
        from repro.api.artifact import save_artifact

        return save_artifact(self, path, verify=verify)

    @classmethod
    def load(cls, path: str, verify: bool = True) -> "ToadModel":
        """Load a .toad artifact (or a legacy pre-versioning .npz bundle).

        Goes through :func:`repro.api.artifact.load_checked` — the same
        toadcheck-then-load admission path the serving engine, the serve
        CLI and the fleet registry use.
        """
        from repro.api.artifact import load_checked

        return load_checked(path, verify=verify).model

    def __repr__(self) -> str:
        state = (
            "unfitted"
            if not self.is_fitted
            else f"trees={int(self.forest.n_trees)}"
            + (
                f", compressed[{self.spec.name if self.spec else '?'}]"
                if self.is_compressed
                else ""
            )
        )
        return f"ToadModel(task={self.config.task!r}, {state})"
