"""The split of device time by the trainer's phase scopes (``bench/scopes.py``).

``trainer_v5e_small.hlo.txt`` holds instructions of the trainer compiled for
a described TPU v5e (4096 rows, 28 features, 256 bins, depth 4, 2 rounds),
their ``backend_config`` left out; ``trainer_op_raw_s.json`` gives device
seconds by raw instruction name, as the trace reduction keeps them.
"""

import json
import pathlib

import pytest

import bench_tiny
from bench import harness, scopes

HERE = pathlib.Path(__file__).resolve().parent
READERS = {
    "train.grad_s": "grad", "train.hist_prep_s": "hist", "train.split_s": "split",
    "train.commit_s": "commit", "train.route_s": "route", "train.leaf_s": "leaf",
    "train.update_s": "update", "train.unscoped_s": "unscoped",
}


def _fixture():
    text = (HERE / "data" / "trainer_v5e_small.hlo.txt").read_text()
    op_raw_s = json.loads((HERE / "data" / "trainer_op_raw_s.json").read_text())
    return scopes.parse(text), op_raw_s


def _run(trace_summary, rounds=2):
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    wl, config, traffic = harness.find_cell(spec, "higgs.train")
    run = harness.Run(workload=wl, config=config, traffic=traffic, seed=1,
                      seconds=1.0, trace=True, t_process=0.0, devices=[])
    run.trace_summary, run.counters["rounds"] = trace_summary, rounds
    return run


def test_map_takes_the_innermost_phase_of_each_instruction():
    phases, _ = _fixture()
    assert phases["histogram.44"] == "hist"
    assert phases["fusion.248"] == "hist"         # its root is the wrapper's transpose
    assert phases["pad.341"] == "grad"            # inside a fused computation
    assert phases["broadcast_select_fusion.32"] == "commit"
    assert phases["pad_bitcast_fusion.5"] == "route"
    for unscoped in ("copy.303", "slice_reduce_fusion.39", "broadcast_add_fusion.22",
                     "dynamic_update_slice.73", "reduce_sum.188", "constant.3915"):
        assert unscoped not in phases
    assert set(phases.values()) == set(scopes.PHASES)


def test_phase_sums():
    phases, op_raw_s = _fixture()
    got = scopes.split(op_raw_s, phases)
    want = {
        "kernel": 1.0 + 0.5 + 0.25,   # every histogram call, mapped or not
        "hist": 0.25 + 0.125 + 0.0625,
        "grad": 0.1,
        "split": 0.2 + 0.05,
        "commit": 0.03,
        "route": 0.4 + 0.6,
        "leaf": 0.01 + 0.3,
        "update": 0.02 + 0.005,
        # XLA's copies and the cumsum rewrite, the scan's stacked outputs, the
        # entry computation, and names from other programs
        "unscoped": 0.004 + 0.002 + 0.001 + 0.0005 + 0.0001 + 0.0002 + 0.003 + 0.002,
    }
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(sum(op_raw_s.values()))


def test_readers_divide_by_rounds(monkeypatch):
    phases, op_raw_s = _fixture()
    monkeypatch.setattr(scopes, "phase_map", lambda run: phases)
    run = _run({"op_raw_s": op_raw_s}, rounds=4)
    want = scopes.split(op_raw_s, phases)
    for name, phase in READERS.items():
        got = harness.load_piece("metrics", name).read(run, {})
        assert got == pytest.approx(want[phase] / 4), name
    total = sum(harness.load_piece("metrics", n).read(run, {}) for n in READERS)
    assert total == pytest.approx((sum(op_raw_s.values()) - want["kernel"]) / 4)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_a_trace_or_scopes_reads_none(name, monkeypatch):
    reader = harness.load_piece("metrics", name)
    assert reader.read(_run(None), {}) is None
    # a program without the scopes (the parent of this change) maps nothing
    monkeypatch.setattr(scopes, "phase_map", lambda run: {})
    assert reader.read(_run({"op_raw_s": {"fusion.1": 1.0}}), {}) is None


def test_map_of_a_tiny_run_comes_from_the_cache(monkeypatch):
    """The map is read from the program the run compiled, with every phase."""
    from repro.gbdt import trainer

    monkeypatch.setattr(scopes, "_maps", {})
    run = bench_tiny.tiny_run("higgs.train")
    counter = harness.CompileCounter()
    counter.armed = True
    phases = scopes.phase_map(run)
    counter.armed = False
    assert counter.count == 0
    assert set(phases.values()) == set(scopes.PHASES)
    assert trainer.PHASES == tuple(f"toad.{p}" for p in scopes.PHASES)
    assert scopes.phase_map(run) is phases
    assert run.counters["scope_map_instructions"] == len(phases)
