"""The paper's DCNN architectures (Fig. 4): WGAN-GP generators for MNIST and
CelebA plus mirrored CNN critics.

The generator's deconvolution layers run through a selectable backend:
  * "reverse_loop" — the paper's algorithm, phase-decomposed pure JAX
                     (differentiable; used for training),
  * "pallas"       — the reverse-loop Pallas TPU kernel (inference),
  * "pallas_sparse"— the static zero-skipping kernel (pruned inference),
  * "xla"          — conventional zero-insertion conv_transpose (the
                     GPU-style baseline of Table II).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.deconv import deconv2d_reverse_loop, deconv2d_zero_insertion
from ..core.tiling import DeconvGeometry
from ..dist.context import constrain
from . import nn


@dataclasses.dataclass(frozen=True)
class DeconvLayerCfg:
    c_in: int
    c_out: int
    kernel: int
    stride: int
    padding: int
    activation: str  # relu | tanh


@dataclasses.dataclass(frozen=True)
class DcnnConfig:
    """A deconv tower: input root -> stacked deconv layers -> image.

    The original two networks are latent-rooted WGAN generators (input
    is a flat ``(z_dim,)`` vector reshaped to a 1x1 spatial root), but
    the tower itself is workload-agnostic: ``in_hw > 1`` declares an
    *image-rooted* tower (super-resolution heads, denoising decoders)
    whose input is ``(in_hw, in_hw, in_c)`` with ``in_c ==
    layers[0].c_in``.  Every consumer of the config — kernels, plans,
    quantization, serving — keys off `input_shape`/`geometries()`, so
    the two roots share one execution surface (see `repro.workloads`).
    """

    name: str
    z_dim: int
    img_hw: int
    img_c: int
    layers: Tuple[DeconvLayerCfg, ...]
    dtype: str = "float32"
    in_hw: int = 1

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def in_c(self) -> int:
        """Input channel count of the tower root (== layers[0].c_in)."""
        return self.layers[0].c_in

    @property
    def is_latent(self) -> bool:
        """True for the WGAN-style 1x1 latent root (flat z input)."""
        return self.in_hw == 1

    @property
    def input_shape(self) -> Tuple[int, ...]:
        """Per-example input shape: ``(z_dim,)`` for latent towers,
        ``(in_hw, in_hw, in_c)`` for image-rooted towers."""
        if self.is_latent:
            return (self.z_dim,)
        return (self.in_hw, self.in_hw, self.in_c)

    def geometries(self) -> List[DeconvGeometry]:
        h = w = self.in_hw
        out = []
        for l in self.layers:
            g = DeconvGeometry(h, w, l.c_in, l.c_out, l.kernel, l.stride, l.padding)
            out.append(g)
            h, w = g.out_h, g.out_w
        return out


MNIST_DCNN = DcnnConfig(
    name="dcnn-mnist",
    z_dim=100,
    img_hw=28,
    img_c=1,
    layers=(
        DeconvLayerCfg(100, 256, 7, 1, 0, "relu"),   # 1x1 -> 7x7
        DeconvLayerCfg(256, 128, 4, 2, 1, "relu"),   # 7x7 -> 14x14
        DeconvLayerCfg(128, 1, 4, 2, 1, "tanh"),     # 14x14 -> 28x28
    ),
)

CELEBA_DCNN = DcnnConfig(
    name="dcnn-celeba",
    z_dim=100,
    img_hw=64,
    img_c=3,
    layers=(
        DeconvLayerCfg(100, 1024, 4, 1, 0, "relu"),  # 1x1 -> 4x4
        DeconvLayerCfg(1024, 512, 4, 2, 1, "relu"),  # 4x4 -> 8x8
        DeconvLayerCfg(512, 256, 4, 2, 1, "relu"),   # 8x8 -> 16x16
        DeconvLayerCfg(256, 128, 4, 2, 1, "relu"),   # 16x16 -> 32x32
        DeconvLayerCfg(128, 3, 4, 2, 1, "tanh"),     # 32x32 -> 64x64
    ),
)


# ---------------------------------------------------------------------------
# Generator
# ---------------------------------------------------------------------------
def tower_input(cfg: DcnnConfig, x: jax.Array) -> jax.Array:
    """Canonicalize a tower input to the 4D root ``(B, in_hw, in_hw,
    in_c)``.

    Latent towers take flat ``(B, z_dim)`` latents (reshaped onto the
    1x1 spatial root, the WGAN convention); image-rooted towers take
    ``(B, in_hw, in_hw, in_c)`` images directly.  A shape that matches
    neither is a workload mix-up (e.g. latents submitted to an SR head)
    and fails loudly instead of reshaping into silently wrong images."""
    expect = (cfg.in_hw, cfg.in_hw, cfg.in_c)
    if cfg.is_latent and x.ndim == 2 and x.shape[1] == cfg.z_dim:
        return x.reshape(x.shape[0], 1, 1, cfg.z_dim)
    if x.ndim == 4 and tuple(x.shape[1:]) == expect:
        return x
    want = (f"(B, {cfg.z_dim})" if cfg.is_latent
            else f"(B, {expect[0]}, {expect[1]}, {expect[2]})")
    raise ValueError(
        f"{cfg.name} expects input rows shaped {want}; got {x.shape}")


def generator_init(key, cfg: DcnnConfig):
    ks = jax.random.split(key, len(cfg.layers))
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    for i, (k, l) in enumerate(zip(ks, cfg.layers)):
        kw, kb = jax.random.split(k)
        fan_in = l.c_in * l.kernel * l.kernel
        p[f"l{i}"] = {
            "w": nn.lecun_init(kw, (l.kernel, l.kernel, l.c_in, l.c_out),
                               cfg.jdtype, fan_in=fan_in),
            "b": jnp.zeros((l.c_out,), cfg.jdtype),
        }
        s[f"l{i}"] = {"w": (None, None, "cin", "cout"), "b": ("cout",)}
    return p, s


def _tile_kwargs(t) -> Dict[str, int]:
    """A tile override is a square extent (int) or a full autotuner
    TileChoice (kernels.autotune) carrying all four tile factors."""
    if t is None:
        return {}
    if isinstance(t, int):
        return {"t_oh": t, "t_ow": t}
    return t.as_kwargs()


def generator_apply(
    p, cfg: DcnnConfig, z: jax.Array, backend: str = "reverse_loop",
    tile_overrides: Optional[Dict[int, Any]] = None,
    sparse_plans: Optional[Dict[int, Any]] = None,
    return_intermediates: bool = False,
    plan=None,
):
    """z: (B, z_dim) latents — or (B, in_hw, in_hw, in_c) images for an
    image-rooted tower — -> images (B, H, W, C) in [-1, 1].

    ``plan`` is a `repro.plan.NetworkPlan` (fp32 precision): the backend,
    per-layer tiles, fused epilogues and zero-skip schedules all come
    pinned from the plan — the preferred serving path (int8 plans run
    through `quant.infer.quantized_generator_apply` instead).  Without a
    plan, ``backend`` selects the formulation, ``tile_overrides`` maps
    layer index -> TileChoice / square extent, and ``sparse_plans`` maps
    layer index -> precomputed `make_sparse_plan` result for
    backend="pallas_sparse" (see serve.DcnnServeEngine).

    On the pallas backends each layer's kernel is named with its index
    (``deconv2d_l<i>_...``, see `kernels.deconv2d.kernel.kernel_name`),
    and its bias + activation run fused in the kernel's flush phase, so
    the chain never materializes a pre-activation layer in HBM; the other
    backends apply the activation separately.
    ``return_intermediates=True`` additionally returns the list of
    per-layer *inputs* (the tensors quantization calibrates against —
    see quant.calibrate): ``(images, [x_0, ..., x_{L-1}])``.
    """
    if plan is not None:
        if plan.precision != "fp32":
            raise ValueError(
                f"generator_apply executes fp32 plans; a {plan.precision!r} "
                "plan runs through quant.infer.quantized_generator_apply")
        plan.validate_for(cfg)
        backend = plan.backend
    x = tower_input(cfg, z).astype(cfg.jdtype)
    x = constrain(x, "batch", None, None, None)
    inters = []
    for i, l in enumerate(cfg.layers):
        if return_intermediates:
            inters.append(x)
        w, b = p[f"l{i}"]["w"], p[f"l{i}"]["b"]
        lp = plan.layers[i] if plan is not None else None
        fused = backend in ("pallas", "pallas_sparse")
        if backend == "reverse_loop":
            x = deconv2d_reverse_loop(x, w, b, l.stride, l.padding)
        elif backend == "xla":
            x = deconv2d_zero_insertion(x, w, b, l.stride, l.padding)
        elif backend == "pallas":
            from ..kernels.deconv2d import deconv2d
            from ..kernels.deconv2d.ops import suppress_tile_warnings
            if lp is not None:
                x = deconv2d(x, w, b, plan=lp, layer=i)
            else:
                # supported legacy override surface: the expansion into
                # tile kwargs is ours, not the user's — don't warn
                with suppress_tile_warnings():
                    x = deconv2d(
                        x, w, b, l.stride, l.padding,
                        activation=l.activation, layer=i,
                        **_tile_kwargs((tile_overrides or {}).get(i)))
        elif backend == "pallas_sparse":
            from ..kernels.deconv2d.ops import suppress_tile_warnings
            from ..kernels.deconv2d_sparse import deconv2d_sparse
            if lp is not None:
                x = deconv2d_sparse(x, w, b, plan=lp, layer=i)
            else:
                with suppress_tile_warnings():
                    x = deconv2d_sparse(
                        x, w, b, l.stride, l.padding,
                        activation=l.activation, layer=i,
                        plan=(sparse_plans or {}).get(i),
                        **_tile_kwargs((tile_overrides or {}).get(i)))
        else:
            raise ValueError(backend)
        if not fused:
            x = jnp.tanh(x) if l.activation == "tanh" else jax.nn.relu(x)
        x = constrain(x, "batch", None, None, None)
    if return_intermediates:
        return x, inters
    return x


def make_fused_generator(
    cfg: DcnnConfig,
    tiles: Optional[Dict[int, Any]] = None,
    fwd_backend: str = "pallas",
    bwd_backend: str = "reverse_loop",
    plan=None,
):
    """Differentiable generator whose *primal* runs the batch-fused Pallas
    serving kernels and whose *cotangent* runs through the reverse-loop
    formulation's VJP.

    The two backends compute the same function (pinned by the backend
    parity tests), so the gradient is consistent with the forward up to
    kernel-level float reassociation — which lets the WGAN training step
    fill the MXU exactly the way serving does (``tiles`` carries the
    autotuned per-layer batch tile ``t_n``) while staying trainable.  The
    backward pass rematerializes the reverse-loop forward (one extra
    forward per VJP; nothing from the Pallas residuals is reused).

    ``plan`` is a `repro.plan.NetworkPlan`: the primal's backend and
    per-layer tiles (incl. ``t_n``) come pinned from it instead of the
    ``tiles``/``fwd_backend`` pair.

    ``pallas_sparse`` is deliberately rejected: its zero-skip schedule is
    compiled against *frozen* weights, which training mutates every step.
    """
    if plan is not None:
        fwd_backend = plan.backend
        tiles = plan.tile_overrides()
    if fwd_backend == "pallas_sparse":
        raise ValueError(
            "pallas_sparse is inference-only: the static zero-skip plan is "
            "derived from frozen weights, which training updates each step")

    @jax.custom_vjp
    def apply(p, z):
        return generator_apply(p, cfg, z, backend=fwd_backend,
                               tile_overrides=tiles, plan=plan)

    def fwd(p, z):
        return apply(p, z), (p, z)

    def bwd(res, ct):
        p, z = res
        _, vjp = jax.vjp(
            lambda p_, z_: generator_apply(p_, cfg, z_, backend=bwd_backend),
            p, z)
        return vjp(ct)

    apply.defvjp(fwd, bwd)
    return apply


# ---------------------------------------------------------------------------
# Critic (WGAN-GP discriminator: strided convs, LeakyReLU, no norm)
# ---------------------------------------------------------------------------
def critic_init(key, cfg: DcnnConfig):
    chans = [cfg.img_c] + [64 * (2 ** i) for i in range(len(cfg.layers) - 1)]
    ks = jax.random.split(key, len(chans))
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    hw = cfg.img_hw
    for i in range(len(chans) - 1):
        kw, _ = jax.random.split(ks[i])
        fan_in = chans[i] * 16
        p[f"c{i}"] = {
            "w": nn.lecun_init(kw, (4, 4, chans[i], chans[i + 1]), cfg.jdtype,
                               fan_in=fan_in),
            "b": jnp.zeros((chans[i + 1],), cfg.jdtype),
        }
        s[f"c{i}"] = {"w": (None, None, "cin", "cout"), "b": ("cout",)}
        hw = hw // 2
    d_flat = hw * hw * chans[-1]
    p["head"], s["head"] = nn.dense_init(ks[-1], d_flat, 1, cfg.jdtype,
                                         (None, None), bias=True)
    return p, s


def critic_apply(p, cfg: DcnnConfig, x: jax.Array) -> jax.Array:
    n_conv = len([k for k in p if k.startswith("c")])
    for i in range(n_conv):
        x = jax.lax.conv_general_dilated(
            x, p[f"c{i}"]["w"], (2, 2), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p[f"c{i}"]["b"]
        x = jax.nn.leaky_relu(x, 0.2)
        x = constrain(x, "batch", None, None, None)
    x = x.reshape(x.shape[0], -1)
    return nn.dense(p["head"], x)[:, 0]
