"""Pallas TPU kernel: grid-LSH bucket keys for a batch of points.

The paper's per-update hashing cost is O(t·d); for streaming batches this
is an embarrassingly parallel, bandwidth-bound pass over (n, d) — the
natural TPU mapping is one VMEM tile of points per grid step, all t tables
computed in-register, and only the (n, t, 2) int32 keys returned to the
host (the Euler-tour structure consumes keys, never coordinates).

Tiling: X is tiled (block_n, d) in VMEM; eta (t,) and the two mixer
matrices (2, t, d) are small and replicated to every grid step.  The MXU is
not used (integer work); the VPU does floor/mul/add; arithmetic intensity
is ~t ops/byte, so the kernel is HBM-bound by design — the roofline target
is a single straming pass at memory bandwidth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import MIX_A, MIX_B


def _kernel(x_ref, eta_ref, mix_ref, out_ref, *, inv_cell: float, t: int):
    x = x_ref[...]  # (bn, d) f32
    eta = eta_ref[...]  # (t,) f32
    mix = mix_ref[...]  # (2, t, d) i32
    codes = jnp.floor(
        (x[:, None, :] + eta[None, :, None]) * jnp.float32(inv_cell)
    ).astype(jnp.int32)  # (bn, t, d)
    acc_a = jnp.sum(codes * mix[0][None], axis=-1, dtype=jnp.int32)
    acc_b = jnp.sum(codes * mix[1][None], axis=-1, dtype=jnp.int32)

    def _avalanche(h):
        h = h ^ jax.lax.shift_right_logical(h, 16)
        h = h * MIX_A
        h = h ^ jax.lax.shift_right_logical(h, 13)
        h = h * MIX_B
        h = h ^ jax.lax.shift_right_logical(h, 16)
        return h

    out_ref[...] = jnp.stack([_avalanche(acc_a), _avalanche(acc_b)], axis=-1)


@functools.partial(jax.jit, static_argnames=("inv_cell", "block_n", "interpret"))
def lsh_hash(
    x: jnp.ndarray,
    eta: jnp.ndarray,
    mixers: jnp.ndarray,
    *,
    inv_cell: float,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """(n, d) f32 -> (n, t, 2) int32 bucket keys. See ref.lsh_hash."""
    n, d = x.shape
    t = eta.shape[0]
    n_pad = -n % block_n
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    grid = ((n + n_pad) // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, inv_cell=inv_cell, t=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((2, t, d), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, t, 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, t, 2), jnp.int32),
        interpret=interpret,
    )(x.astype(jnp.float32), eta.astype(jnp.float32), mixers.astype(jnp.int32))
    return out[:n]
