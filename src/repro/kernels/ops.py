"""Jitted public wrappers for the Pallas kernels.

``interpret=None`` picks the mode from the backend
(:func:`repro.kernels.orchestration.interpret_mode`): Mosaic on a TPU,
the Pallas interpreter elsewhere.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.orchestration import interpret_mode
from repro.kernels.ssd import ssd_pallas
from repro.kernels.wkv6 import wkv6_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_blk",
                                             "kv_blk", "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_blk: int = 128, kv_blk: int = 128,
                    scale: float | None = None, interpret: bool | None = None):
    """Fused attention. q: (B,S,H,D); k/v: (B,S,KV,D|Dv) → (B,S,H,Dv)."""
    it = interpret_mode() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_blk=q_blk, kv_blk=kv_blk, scale=scale,
                                  interpret=it)


@functools.partial(jax.jit, static_argnames=("chunk", "tile", "interpret"))
def wkv6(r, k, v, lw, u, *, chunk: int = 64, tile: int = 16,
         interpret: bool | None = None):
    """Chunked RWKV6 WKV. r/k/v/lw: (B,S,H,N); u: (H,N)."""
    it = interpret_mode() if interpret is None else interpret
    return wkv6_pallas(r, k, v, lw, u, chunk=chunk, tile=tile, interpret=it)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, d_skip, *, chunk: int = 64,
        interpret: bool | None = None):
    """Mamba2 chunked SSD. x: (B,S,H,P); dt: (B,S,H); b/c: (B,S,G,N)."""
    it = interpret_mode() if interpret is None else interpret
    return ssd_pallas(x, dt, a, b, c, d_skip, chunk=chunk, interpret=it)
