"""The trainer names its work for the profiler.

Host spans (``jax.profiler.TraceAnnotation``) around ``ToadModel.fit`` and
``fit_binned``, and one ``jax.named_scope`` per phase of a round
(``trainer.PHASES``) in the lowered program.  The v5e compile of the scopes
is checked in ``test_tpu_compile.py``.
"""

from __future__ import annotations

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ToadModel
from repro.gbdt import GBDTConfig, trainer

CFG = GBDTConfig(task="binary", n_rounds=2, max_depth=3)
SPANS = ("toad.fit", "toad.fit.inputs", "toad.fit.dispatch")


def _data(n=512, d=4, n_bins=16):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    edges = np.quantile(x, np.linspace(0, 1, n_bins + 1)[1:-1], axis=0).T
    bins = (x[:, :, None] > edges[None]).sum(-1).astype(np.int32)
    return x, y, bins, edges.astype(np.float32)


def _host_spans(tdir) -> dict[str, list[tuple[int, int]]]:
    from jax.profiler import ProfileData

    out: dict[str, list[tuple[int, int]]] = {}
    for f in pathlib.Path(tdir).rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        s = int(ev.start_ns)
                        out.setdefault(ev.name, []).append((s, s + int(ev.duration_ns)))
    return out


@pytest.mark.parametrize("entry", ["fit", "fit_binned"])
def test_fit_writes_nested_host_spans(entry, tmp_path):
    x, y, bins, edges = _data()
    model = ToadModel(config=CFG, n_bins=16)
    call = (lambda: model.fit(x, y)) if entry == "fit" else (
        lambda: model.fit_binned(bins, y, edges))
    call()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        call()
        jax.block_until_ready(model.forest)
    spans = _host_spans(tmp_path)
    assert sorted(spans) == sorted(SPANS), spans
    assert all(len(v) == 1 for v in spans.values()), spans
    (f0, f1), = spans["toad.fit"]
    (i0, i1), = spans["toad.fit.inputs"]
    (d0, d1), = spans["toad.fit.dispatch"]
    assert f0 <= i0 <= i1 <= d0 <= d1 <= f1


def test_lowered_trainer_names_every_phase():
    _, y, bins, edges = _data()
    text = trainer.train_jit.lower(
        CFG, jnp.asarray(bins), jnp.asarray(y), jnp.asarray(edges)
    ).as_text(debug_info=True)
    assert set(re.findall(r"\btoad\.[a-z]+", text)) == set(trainer.PHASES)
