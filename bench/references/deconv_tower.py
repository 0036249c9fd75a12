"""Plain reference of a deconvolution tower, and its weights.

The tower is the paper's WGAN generator (arXiv:2102.00294, Fig. 4): a
latent row reshaped to a 1x1 image, then transposed convolutions, ReLU
after each but the last, tanh after the last.  A transposed convolution
of stride S, kernel K and padding P is written out as its definition: each
input pixel (i, j) adds ``x[i, j] @ w[kh, kw]`` to output pixel
``(i*S - P + kh, j*S - P + kw)``, then the bias.  The products of every
pixel with every tap come from one einsum, and are added into the output
one tap offset at a time.  Nothing here comes from
the program under test.

The arithmetic is the one the configuration states: activations stored in
``cfg["dtype"]``, and each dot taking its operands rounded to
``cfg["dot_operands"]`` (on the chip, float32 operands in one bfloat16 MXU
pass) with exact products and a float32 sum.

Weights are made on the device, from the seed, in one jitted call, in the
layout the program takes: ``{"l<i>": {"w": (K, K, C_in, C_out), "b":
(C_out,)}}``.  Each layer's weights are scaled by the number of input
values that reach one output pixel, so activations keep their size down
the tower and the tanh output spans its range; the biases are not zero, so
the bias path is checked too.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


ACTIVATIONS = {"relu": jax.nn.relu, "tanh": jnp.tanh}


def _layers(cfg):
    h = 1
    for l in cfg["layers"]:
        yield h, l
        h = (h - 1) * l["stride"] + l["kernel"] - 2 * l["padding"]


def jax_key(seed: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def init(cfg, seed: int):
    """The seed's weights, on the default device, in ``cfg["dtype"]``."""
    return _init(cfg_key(cfg), jax_key(seed))


def cfg_key(cfg):
    return (cfg["dtype"], tuple((l["c_in"], l["c_out"], l["kernel"],
                                 l["stride"], l["padding"])
                                for l in cfg["layers"]))


@partial(jax.jit, static_argnums=0)
def _init(key_cfg, key):
    dtype, layers = key_cfg
    params = {}
    h = 1
    for i, (c_in, c_out, k, s, p) in enumerate(layers):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        reach = min(-(-k // s), h) ** 2 * c_in
        gain = 1.0 if i == len(layers) - 1 else 2.0   # tanh last, ReLU before
        params[f"l{i}"] = {
            "w": (jax.random.normal(kw, (k, k, c_in, c_out), jnp.float32)
                  * np.sqrt(gain / reach)).astype(dtype),
            "b": (0.1 * jax.random.normal(kb, (c_out,), jnp.float32)
                  ).astype(dtype),
        }
        h = (h - 1) * s + k - 2 * p
    return params


def forward(cfg, params, z, storage=None, operands=None):
    """Images ``(N, H, W, C)`` for latents ``z`` ``(N, z_dim)``, activations
    stored in ``storage`` and dot operands rounded to ``operands``, each
    the configuration's own unless given."""
    storage = jnp.dtype(storage or cfg["dtype"])
    operands = jnp.dtype(operands or cfg["dot_operands"])
    n = len(z)
    x = jnp.asarray(z, storage).reshape(n, 1, 1, cfg["z_dim"])
    for i, (h, l) in enumerate(_layers(cfg)):
        k, s, p = l["kernel"], l["stride"], l["padding"]
        a = -(-k // s)              # taps along one axis per output phase
        w = params[f"l{i}"]["w"]
        w = jnp.pad(w, ((0, a * s - k), (0, a * s - k), (0, 0), (0, 0)))
        b = params[f"l{i}"]["b"].astype(storage)
        # every input pixel times every tap: t[n, i, j, ai, r, bi, q]
        # lands on output pixel ((i + ai) * s + r, (j + bi) * s + q)
        t = jnp.einsum("nhwc,klcd->nhwkld", x.astype(operands),
                       w.astype(operands),
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST).astype(storage)
        t = t.reshape(n, h, h, a, s, a, s, l["c_out"])
        y = jnp.zeros((n, h + a - 1, s, h + a - 1, s, l["c_out"]), storage)
        for ai in range(a):
            for bi in range(a):
                y = y.at[:, ai:ai + h, :, bi:bi + h, :, :].add(
                    t[:, :, :, ai, :, bi, :, :].transpose(0, 1, 3, 2, 4, 5))
        full = (h + a - 1) * s
        out = (h - 1) * s + k - 2 * p
        y = y.reshape(n, full, full, l["c_out"])[:, p:p + out, p:p + out]
        x = ACTIVATIONS[l["activation"]](y + b)
    return x
