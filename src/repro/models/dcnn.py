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

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.deconv import (LEAKY_SLOPE, deconv2d_reverse_loop,
                           deconv2d_zero_insertion)
from ..core.tiling import ConvGeometry, DeconvGeometry
from ..dist.context import constrain
from . import nn


BN_EPS = 1e-5        # eval-mode BatchNorm's epsilon (PyTorch's default)


@dataclasses.dataclass(frozen=True)
class DeconvLayerCfg:
    """One layer of a tower: a transposed convolution (``kind="deconv"``)
    or a strided forward convolution (``kind="conv"``), ``c_in`` counting
    every channel it reads.

    A layer reads the previous layer's output (the tower's input for the
    first); with ``skip`` it reads ``concat(out[skip], previous)`` along
    the channels, the earlier layer's output first (a U-Net join).
    ``in_activation`` applies to that input, ``norm="batch"`` is an
    eval-mode BatchNorm after the convolution (running statistics,
    applied as written in the step) and ``activation``
    the output nonlinearity after it: relu | tanh | leaky_relu | None."""

    c_in: int
    c_out: int
    kernel: int
    stride: int
    padding: int
    activation: Optional[str]  # relu | tanh | leaky_relu | None
    kind: str = "deconv"
    norm: Optional[str] = None
    skip: Optional[int] = None
    in_activation: Optional[str] = None

    @property
    def is_plain(self) -> bool:
        """A chain layer: a deconvolution reading the previous output."""
        return (self.kind, self.norm, self.skip, self.in_activation) == (
            "deconv", None, None, None)


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

    ``dot_precision`` is the precision of every convolution's products:
    None takes JAX's default (on a TPU, float32 operands in one bfloat16
    MXU pass), ``"highest"`` float32 products (`jax.lax.Precision`).
    """

    name: str
    z_dim: int
    img_hw: int
    img_c: int
    layers: Tuple[DeconvLayerCfg, ...]
    dtype: str = "float32"
    in_hw: int = 1
    dot_precision: Optional[str] = None

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

    @property
    def is_graph(self) -> bool:
        """True when some layer is not a plain chain deconvolution: a
        forward convolution, a BatchNorm, a skip join or an input
        activation (the U-Net generators)."""
        return not all(l.is_plain for l in self.layers)

    def geometries(self) -> List[DeconvGeometry]:
        """Per layer, its geometry: a `ConvGeometry` for ``kind="conv"``.
        A layer's input extent is the previous layer's output extent; a
        skip join must bring an output of the same extent and the
        channels must add up to ``c_in`` (checked here)."""
        h = w = self.in_hw
        out: List[DeconvGeometry] = []
        for i, l in enumerate(self.layers):
            if l.kind not in ("deconv", "conv"):
                raise ValueError(f"{self.name} layer {i}: unknown kind "
                                 f"{l.kind!r}")
            c_in = l.c_in
            if l.skip is not None:
                if not 0 <= l.skip < i - 1:
                    raise ValueError(
                        f"{self.name} layer {i}: skip {l.skip} must name "
                        "an earlier layer than the previous one")
                s = out[l.skip]
                if (s.out_h, s.out_w) != (h, w):
                    raise ValueError(
                        f"{self.name} layer {i}: skip from layer {l.skip} "
                        f"is {s.out_h}x{s.out_w}, the previous output "
                        f"{h}x{w}")
                c_in -= s.c_out
            prev_c = out[-1].c_out if out else self.in_c
            if out and c_in != prev_c:
                raise ValueError(
                    f"{self.name} layer {i} reads {l.c_in} channels; its "
                    f"inputs bring {l.c_in - c_in + prev_c}")
            geom = ConvGeometry if l.kind == "conv" else DeconvGeometry
            g = geom(h, w, l.c_in, l.c_out, l.kernel, l.stride, l.padding)
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


def unet_config(name: str, img_hw: int = 256, in_c: int = 3, out_c: int = 3,
                ngf: int = 64, num_downs: int = 8,
                dtype: str = "float32") -> DcnnConfig:
    """The pix2pix U-Net generator (Isola et al., arXiv:1611.07004, 6.1.1;
    ``UnetGenerator`` of the authors' ``models/networks.py``) in eval mode.

    ``num_downs`` strided K=4, S=2, P=1 convolutions halve the image down
    to 1x1, widening ``ngf`` x 1, 2, 4, 8, 8, ...; each but the first and
    the innermost carries a BatchNorm, and each but the innermost ends in
    LeakyReLU(0.2), whose output is also what the skip carries.  As many
    transposed convolutions double it back, each reading ReLU of its
    input, and each after the first joining the mirror encoder's output:
    BatchNorm on all but the last, tanh on the last.  Layers ``l0`` ..
    ``l<num_downs-1>`` are the encoder, the rest the decoder.

    Its products are float32 (``dot_precision="highest"``): with one
    bfloat16 pass per dot, any float32-level difference (a sum's order)
    grows through the sixteen roundings to about one bfloat16 rounding of
    the output, so two sound programs would differ as much as a program
    computed in bfloat16 differs from either."""
    widths = [ngf * min(2 ** k, 8) for k in range(num_downs)]
    layers = []
    c = in_c
    for k, w in enumerate(widths):
        inner = k == num_downs - 1
        layers.append(DeconvLayerCfg(
            c, w, 4, 2, 1, None if inner else "leaky_relu", kind="conv",
            norm=None if k == 0 or inner else "batch"))
        c = w
    for j in range(num_downs):
        last = j == num_downs - 1
        layers.append(DeconvLayerCfg(
            c if j == 0 else 2 * widths[num_downs - 1 - j],
            out_c if last else widths[num_downs - 2 - j], 4, 2, 1,
            "tanh" if last else None, norm=None if last else "batch",
            skip=None if j == 0 else num_downs - 1 - j,
            in_activation="relu"))
    return DcnnConfig(name=name, z_dim=0, img_hw=img_hw, img_c=out_c,
                      layers=tuple(layers), dtype=dtype, in_hw=img_hw,
                      dot_precision="highest")


PIX2PIX_UNET256 = unet_config("pix2pix-unet256")


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
    """Weights ``{"l<i>": {"w": (K, K, C_in, C_out), "b": (C_out,)}}``;
    a layer with ``norm="batch"`` also holds its running statistics and
    affine, ``"bn": {"gamma", "beta", "mean", "var"}``, at identity."""
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
        if l.norm == "batch":
            one = jnp.ones((l.c_out,), cfg.jdtype)
            zero = jnp.zeros((l.c_out,), cfg.jdtype)
            p[f"l{i}"]["bn"] = {"gamma": one, "beta": zero, "mean": zero,
                                "var": one}
            s[f"l{i}"]["bn"] = {k: ("cout",) for k in p[f"l{i}"]["bn"]}
    return p, s


def activate(x: jax.Array, activation: Optional[str]) -> jax.Array:
    """A layer's nonlinearity, outside a fused kernel epilogue."""
    if activation is None or activation == "none":
        return x
    if activation == "relu":
        return jax.nn.relu(x)
    if activation == "tanh":
        return jnp.tanh(x)
    if activation == "leaky_relu":
        return jax.nn.leaky_relu(x, LEAKY_SLOPE)
    raise ValueError(f"unknown activation {activation!r}")


def batch_norm(x: jax.Array, bn) -> jax.Array:
    """Eval-mode BatchNorm over the last axis, from running statistics."""
    scale = bn["gamma"] / jnp.sqrt(bn["var"] + BN_EPS)
    return (x - bn["mean"]) * scale + bn["beta"]


def normalize(y: jax.Array, q) -> jax.Array:
    """A layer's BatchNorm on its convolution's output ``y``, taken
    without bias: the bias, then ``batch_norm``."""
    return batch_norm(y + q["b"], q["bn"])


def conv2d(x: jax.Array, w: jax.Array, b: jax.Array, stride: int,
           padding: int) -> jax.Array:
    """A strided forward convolution, NHWC x HWIO, plus bias: XLA's
    convolution, the kernel of every ``kind="conv"`` layer."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b


def dot_precision(cfg: DcnnConfig):
    """The context a tower's products run in: ``cfg.dot_precision`` where
    set (`jax.default_matmul_precision`, which reaches XLA's convolutions
    and the Pallas kernels' dots alike), else the caller's."""
    if cfg.dot_precision is None:
        return contextlib.nullcontext()
    return jax.default_matmul_precision(cfg.dot_precision)


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

    On the pallas backends each deconvolution layer's kernel is named
    with its index (``deconv2d_l<i>_...``, see
    `kernels.deconv2d.kernel.kernel_name`), and its bias + activation run
    fused in the kernel's flush phase, so the chain never materializes a
    pre-activation layer in HBM; the other backends apply the activation
    separately.  Forward-convolution layers run XLA's convolution on
    every backend, under the scope ``conv2d_l<i>``; a skip join runs
    under ``skip_concat_l<i>``.  A layer with a BatchNorm (``"bn"``
    running statistics) applies it after the convolution, then its
    activation, outside the kernel.  Every product is taken at
    ``cfg.dot_precision``.
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
    outs = []   # every layer's output, for the skip joins
    with dot_precision(cfg):
        for i, l in enumerate(cfg.layers):
            if l.skip is not None:
                with jax.named_scope(f"skip_concat_l{i}"):
                    x = jnp.concatenate([outs[l.skip], x], axis=-1)
            x = activate(x, l.in_activation)
            if return_intermediates:
                inters.append(x)
            q = p[f"l{i}"]
            lp = plan.layers[i] if plan is not None else None
            # the kernel epilogue adds the bias and applies the activation
            # unless a BatchNorm has to come between them (`normalize`)
            normed = "bn" in q
            w = q["w"]
            b = jnp.zeros_like(q["b"]) if normed else q["b"]
            fused = backend in ("pallas", "pallas_sparse") and not normed
            act = l.activation if fused else None
            if l.kind == "conv":
                fused = True   # its BatchNorm and activation run in its scope
                with jax.named_scope(f"conv2d_l{i}"):
                    x = conv2d(x, w, b, l.stride, l.padding)
                    if normed:
                        x = normalize(x, q)
                    x = activate(x, l.activation)
            elif backend == "reverse_loop":
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
                            activation=act, layer=i,
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
                            activation=act, layer=i,
                            plan=(sparse_plans or {}).get(i),
                            **_tile_kwargs((tile_overrides or {}).get(i)))
            else:
                raise ValueError(backend)
            if not fused:
                if normed:
                    x = normalize(x, q)
                x = activate(x, l.activation)
            x = constrain(x, "batch", None, None, None)
            outs.append(x)
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
        x = activate(conv2d(x, p[f"c{i}"]["w"], p[f"c{i}"]["b"], 2, 1),
                     "leaky_relu")
        x = constrain(x, "batch", None, None, None)
    x = x.reshape(x.shape[0], -1)
    return nn.dense(p["head"], x)[:, 0]
