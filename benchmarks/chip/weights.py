"""Seeded random weights, made on the device in one jitted call.

The layout is the serving program's parameter pytree, written out here from
the configuration file alone, so that the plain references can rebuild the
same weights from the seed without importing the program. ``run.py`` checks
this layout against the program's own before it hands the weights over.

Scales: projections are N(0, 1/fan_in), so each block adds O(1) to the
residual stream; the tied embedding is N(0, (4/sqrt(d))^2), so logits have
a spread of about 4; norm gains are 1 + N(0, 0.01) (the program stores the
offset from 1). A family's own leaves take the initial values that its
file (``families/<family>.py``) gives.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp

import families


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def layout(c: dict) -> dict:
    """{path tuple: (shape, dtype name)} of the program's parameters for
    configuration ``c`` (the ``run`` block of a configuration file), as its
    family lays them out."""
    return families.load(c["family"]).layout(c)


def _leaf(family, key, path, shape, dtype):
    own = family.leaf(key, path, shape, dtype)
    if own is not None:
        return own
    name = path[-1]
    dt = jnp.dtype(dtype)
    if name == "scale":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    if name == "embed":
        std = 4.0 / shape[1] ** 0.5
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    if name == "conv_b":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / fan_in ** 0.5).astype(dt)


def weights_seed(seed: int) -> int:
    return zlib.crc32(f"weights:{int(seed)}".encode()) & 0x7FFFFFFF


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, family, spec):          # family: its module
    flat = {}
    for i, (path, shape, dtype) in enumerate(spec):
        flat[path] = _leaf(family, jax.random.fold_in(key, i), path, shape,
                           dtype)
    return flat


def make(c: dict, seed: int) -> dict:
    """The weights of configuration ``c`` for ``seed``, as the program's
    nested dict, made on the default device in one call."""
    spec = tuple((p, s, d) for p, (s, d) in sorted(layout(c).items()))
    flat = _make(jax.random.PRNGKey(weights_seed(seed)),
                 families.load(c["family"]), spec)
    out: dict = {}
    for path, arr in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = arr
    return out
