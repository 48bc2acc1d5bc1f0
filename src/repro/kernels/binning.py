"""Pallas TPU kernel: quantile binning (bucketize) of raw features.

``bin = #{edges < x}`` computed by broadcast-compare against the edge table
held in VMEM.  The table arrives transposed, ``(E, d)``, and padded with
+inf rows to a multiple of 8; the kernel walks it eight edge rows at a time
(one aligned sublane-tile load per loop step, then static row slices), so
the working set stays one sample tile.  Pure VPU work; the sample tile
streams, the edge table is resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 512
EDGE_ROWS = 8


def _kernel(x_ref, edges_ref, out_ref):
    x = x_ref[...]                      # (TILE, d)

    def chunk(c, acc):
        lo = pl.multiple_of(c * EDGE_ROWS, EDGE_ROWS)
        e = edges_ref[pl.ds(lo, EDGE_ROWS), :]      # (EDGE_ROWS, d)
        for r in range(EDGE_ROWS):      # +inf edges never count
            acc = acc + jnp.where(x > e[r:r + 1, :], 1, 0)
        return acc

    out_ref[...] = jax.lax.fori_loop(
        0, edges_ref.shape[0] // EDGE_ROWS, chunk,
        jnp.zeros(x.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def binning(x, edges, *, interpret: bool = True):
    """(n, d) floats × (d, E) edges -> (n, d) int32 bin ids."""
    n, d = x.shape
    E = edges.shape[1]
    n_pad = -n % TILE
    x = jnp.pad(x.astype(jnp.float32), ((0, n_pad), (0, 0)))
    e_pad = -E % EDGE_ROWS if E else EDGE_ROWS
    edges_t = jnp.pad(edges.astype(jnp.float32).T, ((0, e_pad), (0, 0)),
                      constant_values=jnp.inf)

    out = pl.pallas_call(
        _kernel,
        grid=((n + n_pad) // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, d), lambda i: (i, 0)),
            pl.BlockSpec(edges_t.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, d), jnp.int32),
        interpret=interpret,
    )(x, edges_t)
    return out[:n]
