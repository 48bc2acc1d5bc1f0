"""A HIGGS-shaped binned dataset, made on the device from a fixed data seed.

UCI HIGGS (Baldi, Sadowski, Whiteson 2014) has 28 float features per event:
21 low-level kinematic columns (lepton pT, eta, phi; missing energy
magnitude and phi; four jets with pT, eta, phi and a b-tag) and 7 derived
invariant masses (m_jj, m_jjj, m_lv, m_jlv, m_bb, m_wbb, m_wwbb), and a
binary signal label (53% signal).  This stand-in keeps those columns and
their kinds: heavy-tailed momenta, angles, a three-valued b-tag, and masses
computed from the momenta; the label depends on the masses and b-tags, with
noise.

The dataset is part of the configuration (``data_seed``), as users train
on the one HIGGS file.  ``--seed`` only permutes its rows (:func:`permute`),
so every seed trains on the same rows and grows the same trees, up to the
order of fp32 sums.

Binning is the benchmark's own, not the program's: 255 quantile edges per
column from the first ``edge_sample`` rows, duplicates set to +inf, and
``bin = #{edges < x}`` (a row at an edge goes to the lower bin).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BTAG = (0.0, 1.0865, 2.173)   # the three b-tag values of the UCI file
BIN_ROWS = 1 << 16


def _mass(pt1, eta1, phi1, pt2, eta2, phi2):
    """Invariant mass of two massless objects."""
    m2 = 2.0 * pt1 * pt2 * (jnp.cosh(eta1 - eta2) - jnp.cos(phi1 - phi2))
    return jnp.sqrt(jnp.maximum(m2, 0.0))


def features(key, n: int):
    """(x (n, 28) float32, y (n,) float32) of the stand-in."""
    k = jax.random.split(key, 9)
    sig = jax.random.bernoulli(k[0], 0.53, (n,))
    # lepton and four jets; signal events carry harder jets
    pt = jnp.exp(0.55 * jax.random.normal(k[1], (n, 5)) + jnp.log(45.0))
    pt = pt * jnp.where(sig[:, None], 1.12, 1.0)
    eta = jnp.clip(1.1 * jax.random.normal(k[2], (n, 5)), -2.5, 2.5)
    phi = jax.random.uniform(k[3], (n, 5), minval=-np.pi, maxval=np.pi)
    met = jnp.exp(0.6 * jax.random.normal(k[4], (n,)) + jnp.log(38.0))
    met_phi = jax.random.uniform(k[5], (n,), minval=-np.pi, maxval=np.pi)
    p_b = jnp.where(sig[:, None], jnp.array([0.45, 0.2, 0.35]),
                    jnp.array([0.65, 0.15, 0.2]))
    tag = jax.random.categorical(k[6], jnp.log(p_b)[:, None, :], shape=(n, 4))
    btag = jnp.asarray(BTAG, jnp.float32)[tag]

    lep = (pt[:, 0], eta[:, 0], phi[:, 0])
    jet = [(pt[:, i], eta[:, i], phi[:, i]) for i in range(1, 5)]
    nu = (met, jnp.zeros_like(met), met_phi)
    m_jj = _mass(*jet[0], *jet[1])
    m_jjj = jnp.sqrt(m_jj**2 + _mass(*jet[0], *jet[2])**2 + _mass(*jet[1], *jet[2])**2)
    m_lv = _mass(*lep, *nu)
    m_jlv = jnp.sqrt(m_lv**2 + _mass(*jet[0], *lep)**2 + _mass(*jet[0], *nu)**2)
    m_bb = _mass(*jet[2], *jet[3])
    m_wbb = jnp.sqrt(m_bb**2 + m_jj**2)
    m_wwbb = jnp.sqrt(m_wbb**2 + m_lv**2 + m_jlv**2)

    low = [pt[:, 0], eta[:, 0], phi[:, 0], met, met_phi]
    for i in range(1, 5):
        low += [pt[:, i], eta[:, i], phi[:, i], btag[:, i - 1]]
    high = [m_jj, m_jjj, m_lv, m_jlv, m_bb, m_wbb, m_wwbb]
    x = jnp.stack(low + high, axis=1).astype(jnp.float32)

    score = (
        1.5 * sig
        - 0.8 * jnp.abs(jnp.log(m_bb / 110.0))
        - 0.5 * jnp.abs(jnp.log(m_wwbb / 420.0))
        + 0.25 * (btag[:, 2] + btag[:, 3])
        + 1.2 * jax.random.normal(k[7], (n,))
    )
    y = score > jnp.median(score[: 1 << 16])
    flip = jax.random.bernoulli(k[8], 0.05, (n,))
    return x, (y ^ flip).astype(jnp.float32)


def quantile_edges(sample, n_bins: int):
    """(d, n_bins - 1) edges: sample quantiles, duplicates moved to +inf."""
    m = sample.shape[0]
    s = jnp.sort(sample, axis=0).T                        # (d, m)
    q = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    e = s[:, np.floor(q * (m - 1)).astype(np.int32)]     # (d, n_bins - 1)
    keep = jnp.concatenate(
        [jnp.ones((e.shape[0], 1), bool), e[:, 1:] > e[:, :-1]], axis=1)
    return jnp.sort(jnp.where(keep, e, jnp.inf), axis=1)


def bin_rows(x, edges):
    """(n, d) -> (n, d) int32, bin = #{edges < x}, a block of rows at a time."""
    def block(xb):
        one = lambda col, e: jnp.searchsorted(e, col, side="left")
        return jax.vmap(one, in_axes=(-1, 0), out_axes=-1)(xb, edges)

    if x.shape[0] <= BIN_ROWS:
        return block(x).astype(jnp.int32)
    return jax.lax.map(block, x, batch_size=BIN_ROWS).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "n_bins", "edge_sample"))
def _make(key, *, n: int, n_bins: int, edge_sample: int):
    x, y = features(key, n)
    edges = quantile_edges(x[: min(edge_sample, n)], n_bins)
    return bin_rows(x, edges), y, edges


def make(config: dict):
    """(bins (n, 28) int32, y (n,) float32, edges (28, n_bins-1) float32),
    on the device, from the configuration's fixed ``data_seed``."""
    key = jax.random.key(int(config["data_seed"]))
    return _make(key, n=int(config["rows"]), n_bins=int(config["n_bins"]),
                 edge_sample=int(config["edge_sample"]))


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    word = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(1)[0]
    return jax.random.key(int(word))


@jax.jit
def _permute(key, bins, y):
    perm = jax.random.permutation(key, bins.shape[0])
    return bins[perm], y[perm]


def permute(bins, y, seed: int):
    """The same rows in the order that ``seed`` draws."""
    return _permute(seed_key(seed), bins, y)
