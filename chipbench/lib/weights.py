"""Served network weights, drawn on the device from the seed in one jitted
call, in the layout the program's MLP adapter takes: a list of
{"w": (in, out), "b": (out,)} float32 layers.  He-normal weights and
small normal biases (non-zero, so the biases take part in the check)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("sizes",))
def _draw(key, sizes):
    keys = jax.random.split(key, 2 * (len(sizes) - 1))
    layers = []
    for i, (din, dout) in enumerate(zip(sizes[:-1], sizes[1:])):
        w = jax.random.normal(keys[2 * i], (din, dout), jnp.float32) * (
            2.0 / din) ** 0.5
        b = 0.1 * jax.random.normal(keys[2 * i + 1], (dout,), jnp.float32)
        layers.append({"w": w, "b": b})
    return layers


def draw_mlp(key, sizes) -> list:
    return _draw(jnp.asarray(key), tuple(int(s) for s in sizes))


def to_host(layers) -> list:
    return [(np.asarray(l["w"]), np.asarray(l["b"])) for l in layers]
