"""Pallas TPU kernel for the paper's aggregation inner loop (eqs. 12-13):

    out = w + sum_c s_c * (w_c - w),   s_c = alpha_c * p_c * E_c

over stacked client parameters w_stack (C, M).  This is the bandwidth-bound
hot spot of the server update: the naive jnp path materialises the (C, M)
delta tensor in HBM; the kernel streams one (C, block) tile at a time through
VMEM and writes the output in a single pass (1 read of w_stack + 1 read of w
+ 1 write — the HBM lower bound).

Identity used to avoid materialising deltas: sum_c s_c (w_c - w)
  = (s @ w_stack) - (sum_c s_c) * w.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call


def _agg_kernel(s_ref, wstack_ref, w_ref, o_ref):
    s = s_ref[...].astype(jnp.float32)               # (C,)
    ws = wstack_ref[...].astype(jnp.float32)         # (C, bm)
    w = w_ref[...].astype(jnp.float32)               # (bm,)
    mix = jax.lax.dot_general(s[None, :], ws, (((1,), (0,)), ((), ())))[0]
    out = w * (1.0 - jnp.sum(s)) + mix
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_agg(w, w_stack, s, *, block: int = 16384,
              interpret: bool | None = None):
    """w (M,), w_stack (C, M), s (C,) -> (M,): w + sum_c s_c (w_c - w)."""
    C, M = w_stack.shape
    block = min(block, M)
    pad = (-M) % block
    if pad:
        w = jnp.pad(w, (0, pad))
        w_stack = jnp.pad(w_stack, ((0, 0), (0, pad)))
    Mp = M + pad
    out = pallas_call(
        _agg_kernel,
        grid=(Mp // block,),
        in_specs=[
            pl.BlockSpec((C,), lambda i: (0,)),
            pl.BlockSpec((C, block), lambda i: (0, i)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Mp,), w.dtype),
        interpret=interpret,
    )(s, w_stack, w)
    return out[:M]


def fused_agg_tree(w_global, w_stack, s, *, interpret: bool | None = None):
    """Tree-level wrapper: applies ``fused_agg`` leaf-wise (leaves flattened)."""

    def leaf(wg, ws):
        flat = fused_agg(wg.reshape(-1), ws.reshape(ws.shape[0], -1), s,
                         interpret=interpret)
        return flat.reshape(wg.shape)

    return jax.tree.map(leaf, w_global, w_stack)
