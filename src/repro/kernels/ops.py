"""Public jit'd wrappers for the Pallas kernels.

``interpret=None`` (the default) follows `repro.kernels.platform`: compiled
on a TPU, interpreted elsewhere, decided when the program is lowered.  Pass
``True``/``False`` to force a mode.
"""
from __future__ import annotations

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_agg as _agg
from repro.kernels import ssd_scan as _ssd


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             interpret: bool | None = None):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def fused_agg(w, w_stack, s, *, block: int = 16384,
              interpret: bool | None = None):
    return _agg.fused_agg(w, w_stack, s, block=block, interpret=interpret)


def fused_agg_tree(w_global, w_stack, s, *, interpret: bool | None = None):
    return _agg.fused_agg_tree(w_global, w_stack, s, interpret=interpret)
