"""Pallas TPU kernel: blocked eps-neighbour counting (exact DBSCAN core).

The O(n² d) hot spot of Algorithm 1.  Squared distances are computed in the
MXU-friendly form ‖x‖² + ‖y‖² − 2·x·yᵀ with (block_m × d)·(d × block_n)
tiles; the per-row neighbour counts accumulate across the column-block grid
dimension (innermost), so each output tile stays resident in VMEM for a
whole row sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xm_ref, xn_ref, nvalid_ref, out_ref, *, eps2: float, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xm = xm_ref[...]  # (bm, d)
    xn = xn_ref[...]  # (bn, d)
    sm = jnp.sum(xm * xm, axis=-1)
    sn = jnp.sum(xn * xn, axis=-1)
    dots = jax.lax.dot_general(
        xm, xn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    d2 = sm[:, None] + sn[None, :] - 2.0 * dots
    # mask out padding columns (global column index >= n_valid)
    col = j * block_n + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    ok = (d2 <= eps2) & (col < nvalid_ref[0])
    out_ref[...] += jnp.sum(ok, axis=-1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("eps", "block_m", "block_n", "interpret")
)
def eps_neighbor_counts(
    x: jnp.ndarray,
    *,
    eps: float,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """(n, d) -> (n,) int32 counts of points within eps (self included)."""
    n, d = x.shape
    pm = -n % block_m
    pn = -n % block_n
    xp = jnp.pad(x.astype(jnp.float32), ((0, max(pm, pn)), (0, 0)))
    xm = xp[: n + pm]
    xn = xp[: n + pn]
    grid = ((n + pm) // block_m, (n + pn) // block_n)
    nvalid = jnp.array([n], dtype=jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, eps2=eps * eps + 1e-6, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((block_m,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n + pm,), jnp.int32),
        interpret=interpret,
    )(xm, xn, nvalid)
    return out[:n]
