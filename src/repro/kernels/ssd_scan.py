"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

Per (batch, head): chunks are processed sequentially (last grid dim) with the
inter-chunk SSM state carried in a VMEM fp32 scratch (P x N); within a chunk
everything is MXU matmuls on (Q x Q) / (Q x N) / (Q x P) tiles — the
"state-space duality" form, which is exactly the TPU-friendly layout (the
quadratic intra-chunk part feeds the systolic array; the O(S) recurrence is
only across chunks).

Shapes: x (B,S,H,P), dt (B,S,H) fp32, A (H,) fp32, Bm/Cm (B,S,H,N)
(already group-repeated to H).  Output y (B,S,H,P); state stays internal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)        # (Q,)
    A = a_ref[0].astype(jnp.float32)                # scalar
    Bm = b_ref[0, :, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, :, 0].astype(jnp.float32)         # (Q, N)

    dA = dt * A                                     # (Q,) log-decay per step
    cum = jnp.cumsum(dA)                            # (Q,)

    # intra-chunk dual form
    diff = cum[:, None] - cum[None, :]              # (Q, Q)
    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(idx >= jdx, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    M = CB * decay * dt[None, :]
    y = jax.lax.dot(M, x)                           # (Q, P)

    # inter-chunk contribution from the carried state h (P, N)
    h = h_ref[...]
    y += jax.lax.dot_general(Cm * jnp.exp(cum)[:, None], h,
                             (((1,), (1,)), ((), ())))          # (Q, P)

    # state update: h' = exp(sum dA) h + sum_j exp(cum[-1]-cum[j]) dt_j x_j B_j^T
    seg = jnp.exp(cum[-1] - cum) * dt               # (Q,)
    dBx = jax.lax.dot_general(x * seg[:, None], Bm,
                              (((0,), (0,)), ((), ())))         # (P, N)
    h_ref[...] = jnp.exp(jnp.sum(dA)) * h + dBx

    y_ref[0, :, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             interpret: bool | None = None):
    """Chunked SSD scan.  Returns y (B,S,H,P).  S must divide by ``chunk``."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nC = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pallas_call(
        kernel,
        grid=(B, H, nC),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
