"""Device time of the trainer's phases, from its named scopes.

The trainer runs each phase of a round under a ``jax.named_scope`` named
``toad.<phase>`` (``repro.gbdt.trainer.PHASES``), which XLA keeps in every
instruction's ``metadata={op_name="..."}``.  The trace reduction keeps device
seconds per raw instruction name (``op_raw_s``: ``fusion.446``), so joining
the two needs the compiled trainer's map from instruction name to phase:
the innermost ``toad.<phase>`` component of its ``op_name``.

The map comes from the optimized HLO of the program ``fit_binned`` ran: the
configuration of ``bench/cells/train.py`` with the shapes and dtypes
``fit_binned`` passes.  Set-up compiled that program in this process, so
the read finds it in JAX's caches; it is made once per process.

A program without the scopes maps nothing, and every phase reads ``None``.
"""

from __future__ import annotations

import re
import time

from bench import tracing
from bench.harness import log

#: the phases of a round, as the trainer's scopes name them
PHASES = ("grad", "hist", "split", "commit", "route", "leaf", "update")
#: the Pallas histogram kernel, measured on its own (``train.hist_roofline``)
KERNEL = "histogram"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_maps: dict[str, dict[str, str]] = {}


def parse(hlo_text: str) -> dict[str, str]:
    """``{instruction name: phase}`` of every instruction in an HLO module's
    text whose ``op_name`` holds a ``toad.<phase>`` component (the innermost
    one where there are several)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        op = m and _OP_NAME.search(line)
        if not op:
            continue
        scopes = [p[5:] for p in op.group(1).split("/") if p.startswith("toad.")]
        if scopes:
            out[m.group(1)] = scopes[-1]
    return out


def split(op_raw_s: dict[str, float], phases: dict[str, str]) -> dict[str, float]:
    """Device seconds by phase, plus ``kernel`` (every instruction whose
    stable name is the histogram kernel's, whatever its scope) and
    ``unscoped`` (instructions the map does not name: XLA's own copies,
    other programs)."""
    out = dict.fromkeys(PHASES + ("kernel", "unscoped"), 0.0)
    for name, s in op_raw_s.items():
        if tracing.stable_name(name) == KERNEL:
            out["kernel"] += s
        else:
            phase = phases.get(name)
            out[phase if phase in PHASES else "unscoped"] += s
    return out


def compiled_text(run) -> str:
    """Optimized HLO of the trainer as ``fit_binned`` called it in this run:
    uncommitted arrays on the default device, as the training cell passes
    them."""
    import jax
    import jax.numpy as jnp

    from repro.gbdt import train_jit

    c = run.counters
    cfg = run.piece("cells", "train").program_config(run.config)
    rows, features, n_bins = c["rows"], c["features"], c["n_bins"]
    args = (jax.ShapeDtypeStruct((rows, features), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
            jax.ShapeDtypeStruct((features, n_bins - 1), jnp.float32))
    return train_jit.lower(cfg, *args).compile().as_text()


def phase_map(run) -> dict[str, str]:
    """The trainer's ``{instruction name: phase}`` for this run's cell."""
    key = run.workload["name"]
    if key not in _maps:
        t0 = time.perf_counter()
        _maps[key] = parse(compiled_text(run))
        run.counters["scope_map_s"] = time.perf_counter() - t0
        run.counters["scope_map_instructions"] = len(_maps[key])
        log(f"scope map: {len(_maps[key])} instructions in "
            f"{run.counters['scope_map_s']:.3f} s")
    return _maps[key]


def phase_s(run, phase: str) -> float | None:
    """Device seconds per round of ``phase`` (or ``unscoped``) in the traced
    window; ``None`` without a trace or where the program has no scopes."""
    t, rounds = run.trace_summary, run.counters.get("rounds")
    if not t or not rounds:
        return None
    phases = phase_map(run)
    if not phases:
        return None
    return split(t["op_raw_s"], phases)[phase] / rounds
