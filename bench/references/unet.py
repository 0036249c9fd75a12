"""Plain reference of the pix2pix U-Net generator, and its weights.

The generator (Isola et al., arXiv:1611.07004, 6.1.1; ``UnetGenerator``
of the authors' ``models/networks.py``), in eval mode, follows the
configuration's ``layers`` in order.  Layer ``i`` reads the previous
layer's output (the image for the first), first concatenated along the
channels after the output of layer ``skip`` where it names one, and
takes ``in_activation`` of that.  Then its convolution, plus its bias,
then its BatchNorm where ``norm`` is ``"batch"`` -- applied as written,
``(y - mean) / sqrt(var + 1e-5) * gamma + beta`` from running statistics
-- then ``activation``.

Both kinds of layer are written out as their definitions:

* a forward convolution of stride S, kernel K and padding P: output
  pixel (i, j) is the sum over taps of ``x[i*S - P + kh, j*S - P + kw] @
  w[kh, kw]``, zero outside the image;
* a transposed convolution: each input pixel (i, j) adds
  ``x[i, j] @ w[kh, kw]`` to output pixel ``(i*S - P + kh, j*S - P + kw)``.

The products come from one einsum per layer (per tap for a convolution),
added one tap offset at a time.  Nothing here comes from the program
under test.

The arithmetic is the one the configuration states: activations stored in
``cfg["dtype"]``, and each dot taking its operands rounded to
``cfg["dot_operands"]`` (float32 for pix2pix-unet256; bfloat16 would be
the chip's one-pass MXU rounding) with exact products
(``jax.default_matmul_precision("highest")``) and a float32 sum.

Weights are made on the device, from the seed, in one jitted call, in the
layout the program takes: ``{"l<i>": {"w": (K, K, C_in, C_out), "b":
(C_out,)}}``, and ``"bn": {"gamma", "beta", "mean", "var"}`` for a layer
with a BatchNorm.  The weights are scaled by the number of input values
that reach one output pixel, so activations keep their size through the
sixteen layers and the tanh output spans its range.  The BatchNorm's
statistics and affine are drawn away from the identity (mean not 0,
variance and gamma not 1), so that folding them is checked; a bias is
drawn only for the layers whose ``bias`` is true, and is zero elsewhere
(the reference code's convolutions before a BatchNorm have none).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5
LEAKY_SLOPE = 0.2


def _act(x, name):
    if name is None:
        return x
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "leaky_relu":
        return jnp.where(x >= 0, x, LEAKY_SLOPE * x)
    if name == "tanh":
        return jnp.tanh(x)
    raise ValueError(f"unknown activation {name!r}")


def jax_key(seed: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def cfg_key(cfg):
    return (cfg["dtype"], cfg["in_hw"], tuple(
        (l["kind"], l["c_in"], l["c_out"], l["kernel"], l["stride"],
         l["padding"], l.get("norm"), l["activation"], bool(l.get("bias")))
        for l in cfg["layers"]))


def init(cfg, seed: int):
    """The seed's weights, on the default device, in ``cfg["dtype"]``."""
    return _init(cfg_key(cfg), jax_key(seed))


@partial(jax.jit, static_argnums=0)
def _init(key_cfg, key):
    dtype, h, layers = key_cfg
    params = {}
    for i, (kind, c_in, c_out, k, s, p, norm, act, bias) in enumerate(layers):
        kw, kb, kg, kbeta, km, kv = jax.random.split(
            jax.random.fold_in(key, i), 6)
        if kind == "conv":
            reach = k * k * c_in
            h = (h + 2 * p - k) // s + 1
        else:
            reach = min(-(-k // s), h) ** 2 * c_in
            h = (h - 1) * s + k - 2 * p
        # every layer after the first reads a rectified input
        gain = 1.0 if i == 0 else 2.0
        q = {"w": (jax.random.normal(kw, (k, k, c_in, c_out), jnp.float32)
                   * np.sqrt(gain / reach)).astype(dtype),
             "b": ((0.1 * jax.random.normal(kb, (c_out,), jnp.float32))
                   if bias else jnp.zeros((c_out,), jnp.float32)
                   ).astype(dtype)}
        if norm == "batch":
            q["bn"] = {
                "gamma": 1.0 + 0.2 * jax.random.normal(kg, (c_out,)),
                "beta": 0.1 * jax.random.normal(kbeta, (c_out,)),
                "mean": 0.1 * jax.random.normal(km, (c_out,)),
                "var": jnp.exp(0.4 * jax.random.normal(kv, (c_out,))),
            }
            q["bn"] = {n: v.astype(dtype) for n, v in q["bn"].items()}
        params[f"l{i}"] = q
    return params


def _dot_taps(x, w, operands, eq):
    return jnp.einsum(eq, x.astype(operands), w.astype(operands),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def conv(x, w, s, p, operands, storage):
    """Forward convolution as its definition, one tap at a time."""
    n, h, _, _ = x.shape
    k = w.shape[0]
    ho = (h + 2 * p - k) // s + 1
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    y = jnp.zeros((n, ho, ho, w.shape[3]), jnp.float32)
    for kh in range(k):
        for kw in range(k):
            xs = xp[:, kh:kh + s * (ho - 1) + 1:s, kw:kw + s * (ho - 1) + 1:s]
            y = y + _dot_taps(xs, w[kh, kw], operands, "nhwc,cd->nhwd")
    return y.astype(storage)


def deconv(x, w, s, p, operands, storage):
    """Transposed convolution as its definition: every input pixel times
    every tap, added into the output one tap offset at a time."""
    n, h, _, _ = x.shape
    k, c_out = w.shape[0], w.shape[3]
    a = -(-k // s)              # taps along one axis per output phase
    w = jnp.pad(w, ((0, a * s - k), (0, a * s - k), (0, 0), (0, 0)))
    # t[n, i, j, ai, r, bi, q] lands on output pixel
    # ((i + ai) * s + r, (j + bi) * s + q)
    t = _dot_taps(x, w, operands, "nhwc,klcd->nhwkld").astype(storage)
    t = t.reshape(n, h, h, a, s, a, s, c_out)
    y = jnp.zeros((n, h + a - 1, s, h + a - 1, s, c_out), storage)
    for ai in range(a):
        for bi in range(a):
            y = y.at[:, ai:ai + h, :, bi:bi + h, :, :].add(
                t[:, :, :, ai, :, bi, :, :].transpose(0, 1, 3, 2, 4, 5))
    full = (h + a - 1) * s
    out = (h - 1) * s + k - 2 * p
    return y.reshape(n, full, full, c_out)[:, p:p + out, p:p + out]


def forward(cfg, params, x, storage=None, operands=None):
    """Images ``(N, H, W, C)`` for input images ``x`` ``(N, in_hw, in_hw,
    in_c)``, activations stored in ``storage`` and dot operands rounded
    to ``operands``, each the configuration's own unless given."""
    storage = jnp.dtype(storage or cfg["dtype"])
    operands = jnp.dtype(operands or cfg["dot_operands"])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, storage)
        outs = []
        for i, l in enumerate(cfg["layers"]):
            if l.get("skip") is not None:
                x = jnp.concatenate([outs[l["skip"]], x], axis=-1)
            x = _act(x, l.get("in_activation"))
            q = params[f"l{i}"]
            layer = conv if l["kind"] == "conv" else deconv
            y = layer(x, q["w"], l["stride"], l["padding"], operands,
                      storage) + q["b"].astype(storage)
            if l.get("norm") == "batch":
                bn = q["bn"]
                y = ((y - bn["mean"]) / jnp.sqrt(bn["var"] + BN_EPS)
                     * bn["gamma"] + bn["beta"]).astype(storage)
            x = _act(y, l["activation"])
            outs.append(x)
        return x
