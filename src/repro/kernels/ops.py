"""The index's device programs, as the engines call them.

The platform decides where each one runs; no option or environment
variable does:

  * ``lsh_hash`` runs the compiled Pallas kernel (``lsh_hash.py``) when
    JAX's default backend is a TPU, and the jitted jnp reference
    (``ref.py``) on any other backend.
  * ``slot_counts``, ``bucket_core_stats`` and ``core_components`` are
    jitted jnp programs on every backend.  Mosaic refuses a scatter and a
    1-D gather inside a TPU kernel, and XLA compiles them as fused device
    programs, so they have no Pallas body.
"""

from __future__ import annotations

import functools

import jax

from . import lsh_hash as _lh
from . import ref as _ref

_ref_lsh_hash = jax.jit(_ref.lsh_hash, static_argnames=("inv_cell",))


def lsh_hash(x, eta, mixers, *, inv_cell: float):
    """(n, d) f32 -> (n, t, 2) int32 grid-LSH keys; see ``ref.lsh_hash``."""
    if jax.default_backend() == "tpu":
        return _lh.lsh_hash(x, eta, mixers, inv_cell=inv_cell)
    return _ref_lsh_hash(x, eta, mixers, inv_cell=inv_cell)


@functools.partial(jax.jit, static_argnames=("k",))
def bucket_core_stats(slots, sizes, *, k: int):
    """(n, t) slots + (nb,) sizes -> (support, core); see
    ``ref.bucket_core_stats``."""
    return _ref.bucket_core_stats(slots, sizes, k)


@functools.partial(jax.jit, static_argnames=("n_slots",))
def slot_counts(slots, *, n_slots: int):
    """(n, t) slots -> (n_slots,) occupancy histogram; ids outside
    ``[0, n_slots)`` are dropped.  See ``ref.slot_counts``."""
    return _ref.slot_counts(slots, n_slots)


@functools.partial(jax.jit, static_argnames=("n_slots",))
def core_components(slots, core, *, n_slots: int):
    """(n * t,) flat slots + (n,) core mask -> (least, rounds): for each
    of the ``n_slots`` slots the least core row of its component over the
    bucket graph.  The slots come flat because a flat array crosses to the
    chip as it is, where an (n, t) one is relaid on the host into the
    chip's tiled layout first.  See ``ref.core_components``."""
    return _ref.core_components(slots, core, n_slots)
