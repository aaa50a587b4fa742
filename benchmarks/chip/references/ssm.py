"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060,
and the ``Mamba2`` block of ``state-spaces/mamba``): pre-norm residual
blocks of in_proj -> [z | xBC | dt], causal depthwise conv1d with bias and
SiLU over xBC, the selective state-space recurrence, gated RMSNorm
(``norm(y * silu(z))``), out_proj; tied embedding.

Written from that description alone: it imports nothing of the serving
program and reads only the weights that ``weights.py`` rebuilds from the
seed. The recurrence runs one token at a time,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t,

an algorithm independent of the chunked scan that the program serves.
Departures: RMSNorm epsilon is 1e-6 (mamba_ssm uses 1e-5; the difference
is below float32 rounding wherever the mean square is far above 1e-5), and
norm gains are stored as offsets from 1 (``1 + g``), as the program stores
them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from references.common import f32, gaps, highest, mm, rms


def _hidden(c, params, tokens, control):
    B, T = tokens.shape
    d = c["d_model"]
    di = c["expand"] * d
    N, G, P, W = c["d_state"], c["n_groups"], c["ssm_head_dim"], c["d_conv"]
    H = di // P
    x = f32(jnp.take(params["tok"]["embed"], tokens, axis=0))

    def layer(u, p):
        zxbcdt = mm(rms(u, p["ln"]["scale"]), p["w_in"], control)
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:2 * di + 2 * G * N]
        dt = zxbcdt[..., 2 * di + 2 * G * N:]
        w = f32(p["conv_w"])
        padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        conv = sum(padded[:, i:i + T] * w[i] for i in range(W)) + f32(p["conv_b"])
        xbc = jax.nn.silu(conv)
        xs = xbc[..., :di].reshape(B, T, H, P)
        Bm = jnp.repeat(xbc[..., di:di + G * N].reshape(B, T, G, N), H // G, 2)
        Cm = jnp.repeat(xbc[..., di + G * N:].reshape(B, T, G, N), H // G, 2)
        dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B,T,H)
        A = -jnp.exp(p["A_log"])

        def step(h, xs_t):
            x_t, dt_t, b_t, c_t = xs_t
            h = (jnp.exp(dt_t * A)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

        h0 = jnp.zeros((B, H, P, N), jnp.float32)
        _, ys = lax.scan(step, h0, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                                    Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
        y = ys.swapaxes(0, 1) + p["D"][:, None] * xs
        y = rms(y.reshape(B, T, di) * jax.nn.silu(z), p["out_norm"]["scale"])
        return u + mm(y, p["w_out"], control), None

    x, _ = lax.scan(layer, x, params["layers"])
    return rms(x, params["final_norm"]["scale"])


@partial(jax.jit, static_argnums=(0, 4))
def _run(c, params, tokens, targets, control):
    h = _hidden(c, params, tokens, False)
    hc = _hidden(c, params, tokens, True) if control else None
    return gaps(h, hc, params["tok"]["embed"], targets, c["vocab_size"])


def run(c: dict, params, tokens, targets, control: bool = False):
    """(gap of the served token, gap of the control's first choice) at
    every position of ``tokens`` (B,T); see ``references.common.gaps``."""
    return highest(_run)(c, params, tokens, targets, control)
