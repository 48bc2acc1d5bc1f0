"""Training-histogram implementations: Pallas MXU kernel + fused jnp path.

LightGBM's histogram step is a random scatter-add — hostile to TPUs.  Two
scatter-free implementations live here, both behind the
``repro.kernels.ops.build_histogram`` dispatch, and both compute the same
contraction: per feature, the bin one-hot against a node-expanded channel
matrix ``A[s, node*CH + c] = gh[s, c] * [pos[s] == node]``,

    hist[node, f, b, c] = sum_s [bins[s, f] == b] * A[s, node*CH + c].

``histogram`` (Pallas, the TPU path): the bins arrive transposed, ``(d, n)``,
as do the channels, ``(CH, n)``, and node ids, ``(1, n)``: samples sit on
lanes, a grid step reads a ``(FEATURE_BLOCK, TILE)`` bins block, every
block obeys the (8, 128) tiling rule, and no input is padded to 128 lanes
in HBM.  The step builds ``A`` (transposed) for its sample tile in VMEM
and, per feature, the ``(n_bins, TILE)`` one-hot, and contracts them on the
MXU into the feature's ``(n_bins, nodes*CH)`` output rows.  Grid: (feature blocks,
sample tiles) — the sample-tile axis is innermost, so each output block is
revisited and accumulated in place.  The MXU multiplies bf16: each fp32
channel value is split exactly into three bf16 parts (hi + mid + lo, by
truncating the mantissa), the parts become separate columns of ``A``, and
the three partial histograms are summed in fp32 afterwards, so products are
exact and accumulation is fp32.

``histogram_fused`` (jnp, the CPU/GPU fast path): the same contraction
expressed as one ``(n_bins, n) @ (n, n_nodes*CH)`` dot_general per feature.
Unlike the segment-sum reference it never materializes an ``(n·d, CH)``
scratch array (XLA's scatter-add is serial on CPU and dominates the
trainer's hot loop), and unlike the Pallas kernel it needs no
sample-padding.  ``A`` is built once and reused by all ``d`` features.

Shared contract (parity-tested in tests/test_kernels.py): fp32
accumulation, identical results to ``ref.histogram_ref`` to <= 1e-5, and
samples with ``pos >= n_nodes`` contribute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512
FEATURE_BLOCK = 8
N_PARTS = 3          # bf16 parts per fp32 channel value
LANES = 128
#: scoped-VMEM ceiling for the kernel (v5e has 128 MiB of VMEM per core)
VMEM_CAP = 96 * 1024 * 1024


def _bf16_parts(a):
    """fp32 -> N_PARTS fp32 arrays, each exactly bf16, summing to ``a``.

    Truncation by bit mask (not a convert round trip, which a compiler may
    fold away): each part keeps the top 8 significant bits of what is left.
    """
    parts = []
    for _ in range(N_PARTS):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)
        parts.append(hi)
        a = a - hi
    return parts


def _kernel(bins_ref, gh_ref, pos_ref, colnode_ref, colsel_ref, out_ref, *,
            n_bins: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    ch = gh_ref.shape[0]
    colsel = colsel_ref[...]                        # (Np, 1) part*CH + c
    vals = jnp.zeros((colsel.shape[0], TILE), jnp.float32)
    for p, part in enumerate(_bf16_parts(gh_ref[...])):
        for c in range(ch):
            vals = jnp.where(colsel == p * ch + c, part[c:c + 1, :], vals)
    # (Np, TILE) node-expanded channels, transposed; exact in bf16
    a = jnp.where(colnode_ref[...] == pos_ref[...], vals, 0.0)
    a = a.astype(jnp.bfloat16)

    bins = bins_ref[...]                            # (FEATURE_BLOCK, TILE)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (n_bins, TILE), 0)
    for j in range(bins.shape[0]):
        onehot = jnp.where(b_iota == bins[j:j + 1, :], 1.0, 0.0)
        rows = slice(j * n_bins, (j + 1) * n_bins)
        # (n_bins, TILE) x (Np, TILE)^T on the MXU -> (n_bins, Np)
        out_ref[rows, :] += jax.lax.dot_general(
            onehot.astype(jnp.bfloat16), a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def bins_on_lanes(bins):
    """(n, d) bins -> the kernel's ``(d_pad, n_pad)`` int32 view, samples on
    lanes: features padded to ``FEATURE_BLOCK``, samples to ``TILE``, both
    with bin 0.  The trainer routes rows through the same expression, so
    within one program XLA keeps a single copy for both."""
    n, d = bins.shape
    return jnp.pad(bins.astype(jnp.int32),
                   ((0, -n % TILE), (0, -d % FEATURE_BLOCK))).T


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "interpret"))
def histogram(bins, gh, pos, *, n_nodes: int, n_bins: int, interpret: bool = True):
    """(n, d) bins × (n, CH) channels × (n,) node ids -> (n_nodes, d, n_bins, CH).

    Drop-in replacement for ref.histogram_ref; validated against it in
    tests/test_kernels.py over shape/dtype sweeps.
    """
    n, d = bins.shape
    CH = gh.shape[1]
    n_pad = -n % TILE
    d_pad = -d % FEATURE_BLOCK
    nb = -(-n_bins // 16) * 16                      # bf16 sublane tile
    # samples on lanes throughout: bins (d, n), channels (CH, n), nodes
    # (1, n), so a grid step's blocks are (FEATURE_BLOCK, TILE), (CH, TILE)
    # and (1, TILE), and no array is padded out to 128 lanes in HBM
    bins_t = bins_on_lanes(bins)
    gh_t = jnp.pad(gh.astype(jnp.float32), ((0, n_pad), (0, 0))).T
    # padding rows carry the out-of-range sentinel: they contribute nothing
    pos = jnp.pad(pos.astype(jnp.int32), (0, n_pad), constant_values=n_nodes)

    n_cols = N_PARTS * n_nodes * CH
    col = np.arange(-(-n_cols // LANES) * LANES)
    colnode = np.where(col < n_cols, col // (N_PARTS * CH), -1)[:, None]
    colsel = (col % (N_PARTS * CH))[:, None]
    n_cp = col.size

    out_block = FEATURE_BLOCK * nb * n_cp * 4
    work = 4 * n_cp * TILE * 4 + 2 * nb * TILE * 4 + 2 * n_cp * LANES * 4
    vmem = min(VMEM_CAP, 2 * out_block + work + (8 << 20))

    out = pl.pallas_call(
        functools.partial(_kernel, n_bins=nb),
        grid=((d + d_pad) // FEATURE_BLOCK, (n + n_pad) // TILE),
        in_specs=[
            pl.BlockSpec((FEATURE_BLOCK, TILE), lambda f, i: (f, i)),
            pl.BlockSpec((CH, TILE), lambda f, i: (0, i)),
            pl.BlockSpec((1, TILE), lambda f, i: (0, i)),
            pl.BlockSpec((n_cp, 1), lambda f, i: (0, 0)),
            pl.BlockSpec((n_cp, 1), lambda f, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((FEATURE_BLOCK * nb, n_cp), lambda f, i: (f, 0)),
        out_shape=jax.ShapeDtypeStruct(((d + d_pad) * nb, n_cp), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(bins_t, gh_t, pos[None, :], jnp.asarray(colnode, jnp.int32),
      jnp.asarray(colsel, jnp.int32))

    # (d, nb, cols) -> (d, B, nodes, parts, CH) -> sum parts -> (nodes, d, B, CH)
    out = out.reshape(d + d_pad, nb, n_cp)[:d, :n_bins, :n_cols]
    out = out.reshape(d, n_bins, n_nodes, N_PARTS, CH).sum(axis=3)
    return out.transpose(2, 0, 1, 3)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def histogram_fused(bins, gh, pos, *, n_nodes: int, n_bins: int):
    """(n, d) bins × (n, CH) channels × (n,) node ids -> (n_nodes, d, n_bins, CH).

    Fused jnp path: per-feature bin one-hot contracted against the
    node-expanded channel matrix on the matrix units — no ``(n·d, CH)``
    scratch array and no scatter.  fp32 accumulation; ``pos`` outside
    ``[0, n_nodes)`` matches no one-hot column and contributes nothing.
    """
    n, d = bins.shape
    CH = gh.shape[1]
    gh = gh.astype(jnp.float32)
    # A[s, node*CH + c] = gh[s, c] * [pos[s] == node] — shared by all features
    node_oh = pos[:, None] == jnp.arange(n_nodes, dtype=jnp.int32)[None, :]
    A = (node_oh[:, :, None] * gh[:, None, :]).reshape(n, n_nodes * CH)
    iota_b = jnp.arange(n_bins, dtype=jnp.int32)[:, None]

    def per_feature(_, col):
        onehot = (iota_b == col[None, :].astype(jnp.int32)).astype(jnp.float32)
        out = jax.lax.dot_general(
            onehot,
            A,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # fp32 products on TPU too
            preferred_element_type=jnp.float32,
        )  # (n_bins, n_nodes*CH)
        return None, out

    _, out = jax.lax.scan(per_feature, None, bins.T)  # (d, n_bins, n_nodes*CH)
    return out.reshape(d, n_bins, n_nodes, CH).transpose(2, 0, 1, 3)
