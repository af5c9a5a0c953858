"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Supports causal masking, sliding windows (Gemma-3 local layers) and GQA
(kv head index = q head index // group).  Grid = (batch·q_heads, q blocks,
kv blocks) with the kv dimension innermost so the (block_q, head_dim)
accumulator and the running (m, l) statistics stay resident in VMEM scratch
across a full kv sweep.

Block sizes default to (128, 128): the (128, dh)·(dh, 128) products keep
the MXU at full occupancy for dh >= 128, and a block working set of
q + k + v + acc ≈ 4 · 128 · dh · 4B ≈ 256 KiB (dh=128) fits VMEM with room
for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU memory spaces; interpret mode simulates them on CPU
    from jax.experimental.pallas import tpu as pltpu

    _SCRATCH = lambda shape: pltpu.VMEM(shape, jnp.float32)  # noqa: E731
except Exception:  # pragma: no cover
    _SCRATCH = lambda shape: pl.MemorySpace.ANY  # type: ignore  # noqa: E731

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window, block_q: int, block_k: int,
    q_offset: int, kv_len: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ) + q_offset
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_len  # padding
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window

    # skip fully-masked blocks cheaply (still traced; predicated on TPU)
    q = q_ref[0].astype(jnp.float32)  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)  # (bk, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "scale",
        "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """GQA flash attention. q: (b, hq, sq, dh); k,v: (b, hkv, skv, dh)."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pq = -sq % block_q
    pk = -skv % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    # flatten (b, h) into one grid axis
    qf = qp.reshape(b * hq, sq + pq, dh)
    kf = kp.reshape(b * hkv, skv + pk, dh)
    vf = vp.reshape(b * hkv, skv + pk, dh)

    grid = (b * hq, (sq + pq) // block_q, (skv + pk) // block_k)

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            window=window,
            block_q=block_q,
            block_k=block_k,
            q_offset=q_offset,
            kv_len=skv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec(
                (1, block_k, dh), lambda h, i, j, g=group: (h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, block_k, dh), lambda h, i, j, g=group: (h // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq + pq, dh), q.dtype),
        scratch_shapes=[
            _SCRATCH((block_q, dh)),
            _SCRATCH((block_q,)),
            _SCRATCH((block_q,)),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq + pq, dh)[:, :, :sq]
