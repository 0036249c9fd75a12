"""int8 inference path for the DCNN generators.

`quantized_generator_apply` is the quantized twin of
`models.dcnn.generator_apply(backend="pallas")`: the calibrated input
scale quantizes z once, then every deconv layer runs the int8 batch-fused
Pallas kernel with its fused requant epilogue re-quantizing straight into
the next layer's calibrated range — the activation chain stays int8 in
HBM end-to-end, with only the final tanh layer emitting f32 images.

Jit/shard_map friendly: the quantized params ride as ordinary traced
arrays (the serving engine replicates them on a mesh exactly like f32
params) while the per-layer scales bake in as compile-time constants of
the per-bucket executable.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..dist.context import constrain
from ..models.dcnn import DcnnConfig, _tile_kwargs, tower_input
from .calibrate import QuantConfig
from .qmath import quantize_symmetric


def quantized_generator_apply(
    qp: Dict[str, Any],
    cfg: DcnnConfig,
    qcfg: Optional[QuantConfig],
    z: jax.Array,
    tile_overrides: Optional[Dict[int, Any]] = None,
    interpret: Optional[bool] = None,
    plan=None,
) -> jax.Array:
    """z: (B, z_dim) f32 -> images (B, H, W, C) f32 in [-1, 1].

    ``qp`` is the `quant.calibrate.quantize_params` tree (int8 ``w_q``,
    f32 ``b``, f32 per-channel combined ``scale``); ``qcfg`` carries the
    calibrated activation scales that chain the layers together.

    ``plan`` is a `repro.plan.NetworkPlan` at precision="int8": per-layer
    tiles AND the requant epilogue scales come pinned from it, and
    ``qcfg`` may be None (the plan carries the calibration)."""
    from ..kernels.deconv2d import deconv2d_int8

    if plan is not None:
        if plan.precision != "int8":
            raise ValueError(
                f"quantized_generator_apply needs an int8 plan, got "
                f"{plan.precision!r}")
        plan.validate_for(cfg)
        if qcfg is None:
            qcfg = plan.quant_config()
    if qcfg is None:
        raise ValueError("quantized_generator_apply needs a QuantConfig "
                         "(directly or via an int8 plan)")
    if len(qcfg.layers) != len(cfg.layers):
        raise ValueError(
            f"QuantConfig has {len(qcfg.layers)} layers; "
            f"{cfg.name} has {len(cfg.layers)}")
    x = tower_input(cfg, z).astype(jnp.float32)
    x = quantize_symmetric(x, qcfg.layers[0].x_scale)
    x = constrain(x, "batch", None, None, None)
    for i, l in enumerate(cfg.layers):
        lq = qp[f"l{i}"]
        if plan is not None:
            x = deconv2d_int8(x, lq["w_q"], lq["scale"], lq["b"],
                              plan=plan.layers[i], interpret=interpret,
                              layer=i)
        else:
            from ..kernels.deconv2d.ops import suppress_tile_warnings

            # supported legacy override surface: the tile-kwarg expansion
            # is ours, not the user's — don't warn
            with suppress_tile_warnings():
                x = deconv2d_int8(
                    x, lq["w_q"], lq["scale"], lq["b"], l.stride,
                    l.padding, activation=l.activation,
                    out_scale=qcfg.out_scale(i), interpret=interpret,
                    layer=i, **_tile_kwargs((tile_overrides or {}).get(i)))
        x = constrain(x, "batch", None, None, None)
    return x


def quantized_generator_ref(
    qp: Dict[str, Any],
    cfg: DcnnConfig,
    qcfg: QuantConfig,
    z: jax.Array,
) -> jax.Array:
    """Fake-quant oracle of the whole chain: the same quantize -> int32
    conv -> requant per layer through `deconv2d_int8_ref` (integer-exact
    accumulation, identical epilogue) — what the Pallas chain is
    parity-tested against end to end."""
    from ..kernels.deconv2d import deconv2d_int8_ref

    x = tower_input(cfg, z).astype(jnp.float32)
    x = quantize_symmetric(x, qcfg.layers[0].x_scale)
    for i, l in enumerate(cfg.layers):
        lq = qp[f"l{i}"]
        x = deconv2d_int8_ref(
            x, jnp.asarray(lq["w_q"]), jnp.asarray(lq["scale"]),
            jnp.asarray(lq["b"]), l.stride, l.padding,
            activation=l.activation, out_scale=qcfg.out_scale(i))
    return x
