"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole tiny run (the harness's look for a chip skipped)
with one fault planted in the program, and the same run without it passes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny


@pytest.fixture(autouse=True)
def fresh_programs():
    # a planted fault must be traced anew, not found in jit's caches
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_training_run_is_correct():
    run = bench_tiny.tiny_run("higgs.train")
    assert run.correct, run.checks
    assert run.compiles_in_window == 0
    assert run.e2e["round_s"] > 0 and run.attempted >= 1


def test_training_state_left_unchanged(monkeypatch):
    from repro.gbdt import trainer

    grow = trainer._grow_tree

    def frozen(*a, **k):
        tree, contrib, n_sp, state = grow(*a, **k)
        return tree, jnp.zeros_like(contrib), n_sp, state

    monkeypatch.setattr(trainer, "_grow_tree", frozen)
    run = bench_tiny.tiny_run("higgs.train")
    assert not run.correct
    assert dict((n, v > lim) for n, v, lim in run.checks)["gain_gap"]


def test_training_on_half_the_batch(monkeypatch):
    from repro.api.model import ToadModel

    fit = ToadModel.fit_binned

    def half(self, bins, y, edges):
        n = bins.shape[0] // 2
        return fit(self, bins[:n], y[:n], edges)

    monkeypatch.setattr(ToadModel, "fit_binned", half)
    run = bench_tiny.tiny_run("higgs.train")
    assert not run.correct
    assert dict((n, v) for n, v, _ in run.checks)["leaf_count"] > 0


def test_training_answer_altered(monkeypatch):
    from repro.api.model import ToadModel

    fit = ToadModel.fit_binned

    def altered(self, bins, y, edges):
        fit(self, bins, y, edges)
        f = self.forest
        slot = f.leaf_ref[0, 0]
        self.forest = dataclasses.replace(
            f, leaf_values=f.leaf_values.at[slot].multiply(-1.0))
        return self

    monkeypatch.setattr(ToadModel, "fit_binned", altered)
    run = bench_tiny.tiny_run("higgs.train")
    assert not run.correct
    assert dict((n, v > lim) for n, v, lim in run.checks)["leaf_gap"]


def _alter_scores(monkeypatch):
    from repro.kernels import ops

    predict = ops.predict_packed_model

    def altered(packed, x):
        out = predict(packed, x)
        return out.at[::16, 0].add(1e-2)

    monkeypatch.setattr(ops, "predict_packed_model", altered)


def test_sound_scoring_run_is_correct():
    run = bench_tiny.tiny_run("covtype.batch")
    assert run.correct, run.checks
    assert run.compiles_in_window == 0


def test_scoring_answer_altered(monkeypatch):
    _alter_scores(monkeypatch)
    assert not bench_tiny.tiny_run("covtype.batch").correct



def test_training_without_the_penalties():
    # the ToaD penalties left out: deep splits take new thresholds where a
    # used one, with its penalty paid, is worth more
    run = bench_tiny.tiny_run(
        "higgs.train",
        program={"toad_penalty_feature": 0.0, "toad_penalty_threshold": 0.0})
    assert not run.correct
    assert dict((n, v > lim) for n, v, lim in run.checks)["split_gap"]
