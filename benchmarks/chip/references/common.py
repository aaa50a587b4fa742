"""Pieces shared by the plain references: float32 matmuls at the highest
precision, the int8 control's quantisation, and the logit gaps.

A served token's gap at a position is how far the reference's logit of
that token lies below the reference's best logit there. For greedy
tokens a sound program reads a gap near rounding noise; a wrong token reads
the spread of the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6
GAP_BLOCK = 256


def f32(a):
    return a.astype(jnp.float32)


def rms(x, g, eps=EPS):
    """RMSNorm with gain ``1 + g`` (the weights store the offset from 1)."""
    x = f32(x)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + f32(g))


def q8(x, axis):
    """Symmetric int8 quantisation along ``axis`` (one scale per slice),
    returned dequantised in float32."""
    x = f32(x)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def mm(x, w, control: bool):
    """x (..., K) @ w (K, N). The control quantises activations per token
    and weights per output channel to int8 (the W8A8 serving path)."""
    if control:
        return jnp.matmul(q8(x, -1), q8(w, 0))
    return jnp.matmul(f32(x), f32(w))


def gaps(h_ref, h_ctl, embed, targets, vocab: int):
    """Logit gaps per position of the final hidden states.

    h_ref (B,T,d): the float32 reference; h_ctl (B,T,d) or None: the
    control's; embed (Vp, d) the tied embedding; targets (B,T) the token
    served at each position. Returns (gap of the served token, gap of the
    token the control puts first or zeros), each (B,T) float32. Logits are
    formed in blocks of positions so that the full (B,T,V) never exists."""
    E = f32(embed[:vocab])
    B, T, d = h_ref.shape
    nb = T // GAP_BLOCK
    hr = h_ref.reshape(B, nb, GAP_BLOCK, d).swapaxes(0, 1)
    tg = targets.reshape(B, nb, GAP_BLOCK).swapaxes(0, 1)
    hc = None if h_ctl is None else h_ctl.reshape(B, nb, GAP_BLOCK, d).swapaxes(0, 1)
    Eq = None if h_ctl is None else q8(embed[:vocab], 1)

    def block(i):
        lg = jnp.einsum("btd,vd->btv", hr[i], E)
        best = jnp.max(lg, -1)
        at = jnp.take_along_axis(lg, tg[i][..., None], -1)[..., 0]
        gp = best - at
        if hc is None:
            return gp, jnp.zeros_like(gp)
        lc = jnp.einsum("btd,vd->btv", q8(hc[i], -1), Eq)
        arg = jnp.argmax(lc, -1)
        gc = best - jnp.take_along_axis(lg, arg[..., None], -1)[..., 0]
        return gp, gc

    gp, gc = lax.map(block, jnp.arange(nb))
    return gp.swapaxes(0, 1).reshape(B, T), gc.swapaxes(0, 1).reshape(B, T)


def highest(fn):
    """Run ``fn`` with float32 matmuls at full precision (on a TPU a float32
    matmul otherwise runs in one bfloat16 pass)."""
    def wrapped(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return wrapped
