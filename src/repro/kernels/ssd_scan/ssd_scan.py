"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the original CUDA
kernel tiles over thread blocks with warp-level matmuls; here each grid step
processes one (batch, head, chunk) with the chunk-local quadratic term on the
MXU and the inter-chunk recurrent state carried in VMEM scratch across the
sequential chunk axis — the state never round-trips to HBM between chunks.

grid = (B, H, num_chunks)   (last axis sequential)
  x  (B,H,nc,Q,P)  inputs pre-scaled by dt      block (1,1,1,Q,P)
  la (B,H,nc,1,Q)  log decay per step (a row)   block (1,1,1,1,Q)
  Bm (B,H,nc,Q,N)  input projection             block (1,1,1,Q,N)
  Cm (B,H,nc,Q,N)  output projection            block (1,1,1,Q,N)
  h0 (B,H,P,N)     initial state                block (1,1,P,N)
outputs:
  y  (B,H,nc,Q,P), h_final (B,H,P,N) (written on the last chunk)

``h0`` seeds the VMEM state scratch on the first chunk, so the serving
engine's chunked prefill can resume a sequence mid-stream (decode-state
slots, DESIGN.md §13) instead of always scanning from zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, la_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, state_scr, *,
            num_chunks: int, Q: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(state_scr.dtype)

    la = la_ref[0, 0, 0].astype(jnp.float32)           # (1,Q)
    x = x_ref[0, 0, 0].astype(jnp.float32)             # (Q,P)
    bm = b_ref[0, 0, 0].astype(jnp.float32)            # (Q,N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)            # (Q,N)

    # prefix sums of the log decay as matmuls with the lower triangle (Mosaic
    # has no cumsum): cum_col[i, :] = cum_row[:, i] = Σ_{k<=i} la[k]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri_f = tri.astype(jnp.float32)
    la_b = jnp.broadcast_to(la, (Q, Q))                # [a, k] = la[k]
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(tri_f, la_b, nt, precision=hi,
                                  preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(la_b, tri_f, nt, precision=hi,
                                  preferred_element_type=jnp.float32)
    cum = cum_col[:, :1]                               # (Q,1)
    total = jnp.sum(la, axis=1, keepdims=True)         # (1,1) = cum[Q-1]

    # intra-chunk: (C B^T ⊙ decay) @ x   — MXU matmuls
    seg = cum_col - cum_row                            # [i,j] = cum_i - cum_j
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    y = jax.lax.dot_general(cb * decay, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q,P)

    # inter-chunk: exp(cum) * C @ state^T
    state = state_scr[...]                             # (P,N)
    y += jnp.exp(cum) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: h <- exp(Σla) h + Σ_q exp(cum_Q - cum_q) x_q ⊗ B_q
    tail = jnp.exp(total - cum)                        # (Q,1)
    new_state = jnp.exp(total) * state + jax.lax.dot_general(
        x * tail, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (P,N)
    state_scr[...] = new_state

    @pl.when(c == num_chunks - 1)
    def _final():
        hout_ref[0, 0] = new_state.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan(x, la, Bm, Cm, h0=None, *, interpret: bool = False):
    """x (B,H,nc,Q,P); la (B,H,nc,Q); Bm/Cm (B,H,nc,Q,N); h0 (B,H,P,N)
    optional initial state (zeros when omitted).
    Returns (y (B,H,nc,Q,P), h_final (B,H,P,N))."""
    B, H, nc, Q, P = x.shape
    N = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    grid = (B, H, nc)
    kernel = functools.partial(_kernel, num_chunks=nc, Q=Q)
    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, la[..., None, :], Bm, Cm, h0.astype(jnp.float32))
    return y, hout
