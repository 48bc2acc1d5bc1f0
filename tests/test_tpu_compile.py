"""Compile-only checks: the main path's Pallas kernels lower for a TPU v5e,
and the trainer's phase scopes survive the v5e compiler.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets ``jit(...).lower(...).compile()`` run Mosaic on
each kernel at real widths (d=256 features, 256 bins, depth 8, 64 trees).
Interpret-mode tests cannot see what this catches: blocks that break the
(8, 128) tiling rule, primitives Mosaic cannot lower, scoped-VMEM overflow.
Every call passes ``interpret=False`` itself — the backend here is the CPU,
so the program's own ``interpret`` gate would pick the interpreter.

The topology is described inside a module-scoped fixture, never at import,
so test workers that never run this file never load the TPU library.
"""

from __future__ import annotations

import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.gbdt import trainer
from repro.kernels import ops
from repro.kernels.binning import binning
from repro.kernels.histogram import histogram
from repro.kernels.predict import _packed_predict_ee_call, packed_predict

D, N_BINS, DEPTH, N_TREES = 256, 256, 8, 64
ROWS = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _packed_shapes(C: int):
    n_fu = 64
    T = N_TREES * C
    return [
        ((ROWS, D), jnp.float32),                          # x
        ((T, 2**DEPTH - 1), jnp.uint32),                   # words
        ((T, 2**DEPTH), jnp.int32),                        # leaf_ref
        ((4096,), jnp.float32),                            # leaf_values
        ((n_fu * (N_BINS - 1),), jnp.float32),             # thr_table
        ((n_fu + 1,), jnp.int32),                          # thr_offsets
        ((n_fu,), jnp.int32),                              # used_features
        ((C,), jnp.float32),                               # base_score
    ]


@pytest.mark.parametrize("C", [1, 7])
def test_packed_predict_compiles(one_chip, C):
    fn = functools.partial(
        packed_predict, max_depth=DEPTH, tidx_bits=8, n_ensembles=C,
        interpret=False)
    _assert_kernel(_compile(fn, *_packed_shapes(C), sharding=one_chip))


@pytest.mark.parametrize("C", [1, 7])
def test_packed_predict_early_exit_compiles(one_chip, C):
    T = N_TREES * C
    tree_block = -(-8 // C) * C
    fn = functools.partial(
        _packed_predict_ee_call, max_depth=DEPTH, tidx_bits=8, n_ensembles=C,
        n_rows=ROWS, guard=0.0, interpret=False)
    shapes = _packed_shapes(C) + [
        ((-(-T // tree_block), C), jnp.float32),           # rem_blocks
        ((C,), jnp.float32),                               # slack
    ]
    _assert_kernel(_compile(fn, *shapes, sharding=one_chip))


@pytest.mark.parametrize("n_nodes", [1, 64, 128])  # 128: depth 8, no subtraction
def test_histogram_compiles(one_chip, n_nodes):
    fn = functools.partial(
        histogram, n_nodes=n_nodes, n_bins=N_BINS, interpret=False)
    compiled = _compile(
        fn, ((ROWS, D), jnp.int32), ((ROWS, 3), jnp.float32),
        ((ROWS,), jnp.int32), sharding=one_chip)
    _assert_kernel(compiled)


def test_binning_compiles(one_chip):
    fn = functools.partial(binning, interpret=False)
    compiled = _compile(
        fn, ((ROWS, D), jnp.float32), ((D, N_BINS - 1), jnp.float32),
        sharding=one_chip)
    _assert_kernel(compiled)


def test_shapes_are_real_widths():
    """The compile tests above run at the widths of configs/toad_gbdt.py."""
    from repro.configs.toad_gbdt import config

    cfg = config()
    assert (cfg.n_features, cfg.n_bins, cfg.gbdt.max_depth) == (D, N_BINS, DEPTH)
    assert np.log2(ROWS) % 1 == 0


# --- the trainer's phase scopes survive the v5e compiler -------------------

#: ops that ``lax.scan``'s body calls ``round_body`` through
ROUND = "jit(train)/while/body/closed_call/"
#: instruction kinds that do a round's work: each must name its phase
PHASE_KINDS = {"fusion", "custom-call", "gather", "scatter", "reduce",
               "dynamic-update-slice"}
#: what runs in the trainer's loops without a phase: XLA's loop-boundary
#: copies, layouts and async copies (no metadata), the TPU rewrite of the
#: cumulative sums (``op_name="reduce_window_sum"``), the call boundary of
#: ``round_body``, and the scan's own counter and stacked per-round outputs
UNSCOPED_KINDS = {
    "add", "and", "bitcast", "broadcast", "compare", "constant", "copy",
    "copy-done", "copy-start", "dynamic-slice", "dynamic-update-slice",
    "fusion", "get-tuple-element", "parameter", "reduce-window", "reshape",
    "select", "slice", "tuple",
}


def _executed(hlo_text: str):
    """(entry name, {computation: [(instruction, kind, op_name)]}) of the
    entry computation and every while-loop body and condition it reaches:
    the computations whose instructions run as device operations."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%([^\s(]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), {"entry": bool(head.group(1)),
                                                   "ins": [], "calls": []})
            continue
        if line.startswith("}"):
            cur = None
            continue
        ins = cur is not None and re.match(
            r"^\s*(?:ROOT )?%([^\s=]+) = .*?\s([a-z][a-z0-9-]*)\(", line)
        if ins:
            op = re.search(r'op_name="([^"]*)"', line)
            cur["ins"].append((ins.group(1), ins.group(2), op.group(1) if op else ""))
            cur["calls"] += re.findall(r"\b(?:body|condition)=%([^\s,]+)", line)
    entry = next(k for k, v in comps.items() if v["entry"])
    todo, seen = [entry], {}
    while todo:
        c = todo.pop()
        if c not in seen:
            seen[c] = comps[c]["ins"]
            todo += comps[c]["calls"]
    return entry, seen


def test_trainer_phase_scopes_compile(one_chip, monkeypatch):
    """Every operation of a round names one phase of ``trainer.PHASES`` in the
    v5e program, and the Pallas call keeps its name ``histogram``."""
    monkeypatch.setattr(ops, "_interp", lambda: False)
    jax.clear_caches()  # trace anew, with the kernel compiled for the chip
    cfg = trainer.GBDTConfig(task="binary", n_rounds=2, max_depth=4,
                             hist_method="pallas", toad_penalty_feature=8.0,
                             toad_penalty_threshold=2.0)
    shapes = [((ROWS, 28), jnp.int32), ((ROWS,), jnp.float32),
              ((28, N_BINS - 1), jnp.float32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = trainer.train_jit.lower(cfg, *args).compile().as_text()
    entry, executed = _executed(text)

    unnamed, seen_phases, unscoped = [], set(), collections.Counter()
    for comp, instructions in executed.items():
        for name, kind, op in instructions:
            scopes = [p for p in op.split("/") if p.startswith("toad.")]
            seen_phases.update(scopes)
            if kind in PHASE_KINDS and op.startswith(ROUND):
                if len(scopes) != 1 or scopes[0] not in trainer.PHASES:
                    unnamed.append((name, op))
            elif comp != entry and not scopes:
                unscoped[kind] += 1
    assert not unnamed, unnamed[:10]
    assert seen_phases == set(trainer.PHASES)
    assert set(unscoped) <= UNSCOPED_KINDS, unscoped
    names = [n for ins in executed.values() for n, _, _ in ins]
    assert any(re.fullmatch(r"histogram(\.\d+)?", n) for n in names)


def _computations(hlo_text: str):
    """{computation: [(kind, output type, op_name, called computations)]}
    for every computation of the program, fused ones included."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([^\s(]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        ins = cur is not None and re.match(
            r"^\s*(?:ROOT )?%[^\s=]+ = (.*?) ([a-z][a-z0-9-]*)\(", line)
        if ins:
            op = re.search(r'op_name="([^"]*)"', line)
            calls = re.findall(r"\b(?:calls|body|condition|to_apply)=%([^\s,]+)", line)
            cur.append((ins.group(2), ins.group(1), op.group(1) if op else "", calls))
    return comps


def _elements(hlo_type: str) -> int:
    """Elements of the largest array in an HLO output type."""
    dims = re.findall(r"[a-z][a-z0-9]*\[([0-9,]*)\]", hlo_type)
    return max((int(np.prod([int(x) for x in d.split(",") if x])) for d in dims),
               default=0)


def test_trainer_routes_without_gathers(one_chip, monkeypatch):
    """At depth 8 the v5e trainer routes rows and reads their leaf values
    without a per-row gather (``trainer._route``, ``trainer._contrib``), and
    its compare-and-select fuses: no ``toad.route`` buffer outgrows the
    ``(d_pad, ROWS)`` bins the histogram kernel reads."""
    monkeypatch.setattr(ops, "_interp", lambda: False)
    jax.clear_caches()
    d = 28
    cfg = trainer.GBDTConfig(task="binary", n_rounds=2, max_depth=DEPTH,
                             hist_method="pallas", toad_penalty_feature=8.0,
                             toad_penalty_threshold=2.0)
    shapes = [((ROWS, d), jnp.int32), ((ROWS,), jnp.float32),
              ((d, N_BINS - 1), jnp.float32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = trainer.train_jit.lower(cfg, *args).compile().as_text()
    comps = _computations(text)

    # a fused gather names its scope in the op_name of the fusion's caller
    caller = {c: op for ins in comps.values() for _, _, op, calls in ins
              for c in calls}
    gathers = [(typ, op + " " + caller.get(comp, ""))
               for comp, ins in comps.items() for kind, typ, op, _ in ins
               if kind == "gather"]
    assert not [g for g in gathers if trainer.ROUTE in g[1]], gathers
    leaf = [g for g in gathers if trainer.LEAF in g[1]]
    assert leaf and all(_elements(t) < ROWS for t, _ in leaf), leaf

    # buffers are the outputs of the instructions that run, not of the
    # instructions inside a fusion
    _, executed = _executed(text)
    route = [(typ, op) for c in executed for _, typ, op, _ in comps[c]
             if trainer.ROUTE in op]
    d_pad = -(-d // 8) * 8
    assert route
    assert all(_elements(t) <= d_pad * ROWS for t, _ in route), max(
        route, key=lambda r: _elements(r[0]))
