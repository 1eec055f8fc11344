"""Pallas TPU flash attention (causal / sliding-window), online-softmax.

TPU-native design (DESIGN.md §3.3): MXU-aligned (block_q x block_k) tiles,
q/k/v blocks staged HBM->VMEM by BlockSpec, fp32 accumulators in VMEM scratch
carried across the sequential k-block grid dimension.  Fully-masked k-blocks
are skipped with ``pl.when`` (causal upper triangle / outside the window).

Grid: (B, H, num_q_blocks, num_k_blocks); the last dim is "arbitrary"
(sequential), so scratch persists across k blocks of one (b, h, q-block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, num_k_blocks: int, seq_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    # block-level skip: entirely above the diagonal / outside the window
    q_max = iq * block_q + block_q - 1
    k_min = ik * block_k
    k_max = k_min + block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_min <= q_max
    if window > 0:
        live &= k_max > iq * block_q - window  # some q in block sees some k

    @pl.when(live)
    def _compute():
        kv_valid = (k_pos < seq_kv)                    # (1, bk) padding guard
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        # zero padded v rows with where (0 * NaN-padding would still be NaN)
        v = jnp.where(kv_valid.reshape(-1, 1),
                      v_ref[0, 0].astype(jnp.float32), 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

        mask = kv_valid
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q, k, v: (B, S, H, D) with H already GQA-repeated.  Returns (B, S, H, D).

    block sizes are clamped to the sequence length (kept MXU-multiples of 128
    in production; tests sweep smaller shapes through interpret mode).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Skv, block_k)
    scale = 1.0 / (D ** 0.5)

    qt = jnp.moveaxis(q, 2, 1)   # (B, H, S, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk, seq_kv=Skv)

    out = pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
