"""The benchmark's arithmetic: work counts, trace reduction, tails."""

import json
import pathlib

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)
from bench import harness, tracing

HERE = pathlib.Path(__file__).resolve().parent


def test_histogram_work_by_hand():
    w = harness.load_piece("work", "histogram")
    # 1000 rows, 4 features, 16 bins, 2 nodes: 1000*4*3 accumulations;
    # bins 16000 B, channels 12000 B, node ids 4000 B, histogram 2*4*16*3*4 B
    assert w.call(1000, 4, 16, 2) == (12000.0, 16000 + 12000 + 4000 + 1536.0)
    assert w.nodes_per_level(4) == [1, 1, 2, 4]
    ops, nbytes = w.round_(1000, 4, 16, 4)
    assert ops == 4 * 12000.0
    assert nbytes == 4 * 32000 + (1 + 1 + 2 + 4) * 4 * 16 * 3 * 4


def test_predict_work_by_hand():
    w = harness.load_piece("work", "predict")
    # 256 rows x 54 features, 14 trees of depth 5, 7 classes
    ops, nbytes = w.call(256, 54, 14, 5, 7)
    assert ops == 256 * 14 * 5
    assert nbytes == 256 * 54 * 4 + 14 * (2 * 31 + 32) * 4 + 256 * 7 * 4


def test_split_gain_work_by_hand():
    w = harness.load_piece("work", "split_gain")
    assert w.round_(4, 16, 2) == 3 * 4 * 15 * 20


def _event(plane, line, name, start, dur):
    return (plane, line, name, start, dur)


def test_busy_union_idle_and_kernel_time():
    dev, host = "/device:TPU:0", "/host:CPU"
    ev = [
        _event(host, "python", "bench.window", 1000, 1000),
        _event(host, "python", "bench.train_job", 1000, 500),
        _event(dev, "XLA Ops", "histogram.73", 1100, 200),     # 1100-1300
        _event(dev, "XLA Ops", "fusion.4", 1250, 100),         # overlaps: 1300-1350 new
        _event(dev, "XLA Ops", "histogram.71", 1600, 100),     # 1600-1700
        _event(dev, "XLA Ops", "copy.2", 1950, 100),           # clipped to 1950-2000
        _event(dev, "XLA Modules", "jit_train", 1000, 1000),   # not an op line
        _event(dev, "XLA Ops", "%while.3 = (s32[]) while(...)", 1000, 1000),  # a container
        _event(dev, "XLA Ops", "fusion.9", 2500, 100),         # outside the window
    ]
    s = tracing.summarize(ev)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx((250 + 100 + 50) * 1e-9)
    assert tracing.kernel_s(s, r"^histogram$") == pytest.approx(300e-9)
    assert s["op_s"]["fusion"] == pytest.approx(100e-9)
    # gaps: 1000-1100 (job), 1350-1600 (job till 1500), 1700-1950 (window only)
    gaps = sorted((round(t * 1e9), n) for n, t in s["idle_gaps"])
    assert gaps == [(100, "bench.train_job"), (250, "bench.train_job"),
                    (250, "bench.window")]

def test_recorded_trace():
    """A trace recorded on a TPU v5e: three histogram calls in bench spans."""
    path = HERE / "data" / "trace_small.json"
    events = [tuple(e) for e in json.loads(path.read_text())]
    s = tracing.summarize(events)
    ops = [e for e in events if e[0].startswith("/device:TPU:0")]
    w = [e for e in events if e[2] == "bench.window"][0]
    inside = [(max(e[3], w[3]), min(e[3] + e[4], w[3] + w[4])) for e in ops]
    union = sum(b - a for a, b in tracing.merge([iv for iv in inside if iv[1] > iv[0]]))
    assert s["busy_s"] == pytest.approx(union / 1e9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert tracing.kernel_s(s, r"^histogram$") > 0


def test_stable_names():
    assert tracing.stable_name("histogram.73") == "histogram"
    assert tracing.stable_name("fusion") == "fusion"
    assert tracing.stable_name("copy-done.4") == "copy-done"



def _sums_by_loop(bins_t, local, nodes, n_bins, w):
    d, n = bins_t.shape
    out = np.zeros((nodes, d, n_bins))
    for i in range(n):
        for f in range(d):
            out[local[i], f, bins_t[f, i]] += 1.0 if w is None else w[i]
    return out


@pytest.mark.parametrize("with_parent", [False, True])
def test_reference_histogram_by_hand(with_parent):
    from concurrent.futures import ThreadPoolExecutor

    ref = harness.load_piece("reference", "train_check")
    rng = np.random.default_rng(3)
    d, n, B, nodes = 3, 400, 8, 4
    bins_t = rng.integers(0, B, (d, n)).astype(np.uint8)
    local = rng.integers(0, nodes, n)
    g, h = rng.normal(size=n), rng.random(n)
    want = np.stack([_sums_by_loop(bins_t, local, nodes, B, w) for w in (g, h, None)])
    parent = None
    if with_parent:
        parent = np.stack([_sums_by_loop(bins_t, local // 2, nodes // 2, B, w)
                           for w in (g, h, None)])
    with ThreadPoolExecutor(2) as pool:
        got = ref.level_histogram(bins_t, local, nodes, B, (g, h, None), pool, parent)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_reference_routing_by_hand():
    from concurrent.futures import ThreadPoolExecutor

    ref = harness.load_piece("reference", "train_check")
    rng = np.random.default_rng(4)
    d, n = 3, 1000
    bins_t = rng.integers(0, 16, (d, n)).astype(np.uint8)
    pos = rng.integers(3, 7, n)                 # level 2 of a depth-3 tree
    feat, thr = rng.integers(0, d, 7), rng.integers(0, 16, 7)
    split = np.array([1, 1, 1, 1, 0, 1, 1], bool)
    with ThreadPoolExecutor(2) as pool:
        child, right = ref.route(bins_t, pos, feat, thr, split, pool, chunk=300)
    for i in range(n):
        p = pos[i]
        go_right = bool(split[p]) and bins_t[feat[p], i] > thr[p]
        assert right[i] == go_right
        assert child[i] == 2 * p + (2 if go_right else 1)
