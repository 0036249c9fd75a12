"""WGAN-GP training (Gulrajani et al. [10]) — the framework the paper uses to
train both DCNNs (Fig. 4).

`WganTrainer` is the training-side mirror of `serve.DcnnServeEngine`:

* **Bucketed step functions.**  Ragged batch sizes are rounded up to
  power-of-two buckets (padded `real` rows are masked out of the loss with
  exact sum/n_valid accounting, the generator's z batch is drawn at the
  bucket size), so a changing data batch re-uses a compiled executable
  instead of tracing a fresh one.  `trace_counts` exposes the guarantee.
* **Mesh sharding.**  With ``mesh=`` the critic and generator steps run as
  data-parallel SPMD via shard_map: params/optimizer states are
  replicated, the batch dim shards the `data` axis per `dist.sharding`
  rules, every shard draws its own z/eps from a per-shard key
  (`jax.random.fold_in` on the shard index), and gradients/metrics are
  `psum`'d so each device applies the identical optimizer update.  The
  single-device path runs the *same* per-shard math in a loop, so a mesh
  run is numerically equivalent to a 1-device run with matching
  ``z_shards``.
* **Batch-fused generator.**  ``backend="pallas"`` routes the generator
  forward through the batch-fused serving kernels (per-bucket tiles, incl.
  the batch tile ``t_n``, autotuned for the per-shard sub-batch) with the
  reverse-loop VJP as the backward — the training step fills the MXU the
  same way serving does.  The default ``reverse_loop`` stays the plain
  differentiable formulation.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..models.dcnn import (DcnnConfig, critic_apply, critic_init,
                           generator_apply, generator_init,
                           make_fused_generator)


def critic_loss(dp, gp_params, cfg: DcnnConfig, real, z, key, gp_coef=10.0,
                mask=None, n_valid=None, gen_fn=None):
    """WGAN-GP critic loss.

    With ``mask``/``n_valid`` the means become ``sum(mask * term) /
    n_valid`` — pad rows of a bucketed batch contribute exactly zero, and
    per-shard values of a sharded batch *sum* to the global loss (the
    divisor is the global valid count, not the shard size)."""
    gen = gen_fn if gen_fn is not None else (
        lambda p, z_: generator_apply(p, cfg, z_))
    fake = gen(gp_params, z)
    d_real = critic_apply(dp, cfg, real)
    d_fake = critic_apply(dp, cfg, fake)
    # gradient penalty on interpolates
    eps = jax.random.uniform(key, (real.shape[0], 1, 1, 1), real.dtype)
    x_hat = eps * real + (1.0 - eps) * fake
    grad_x = jax.grad(lambda x: critic_apply(dp, cfg, x).sum())(x_hat)
    gnorm = jnp.sqrt(jnp.sum(grad_x ** 2, axis=(1, 2, 3)) + 1e-12)
    if mask is None:
        wdist = jnp.mean(d_real) - jnp.mean(d_fake)
        gp = jnp.mean((gnorm - 1.0) ** 2)
    else:
        nv = jnp.asarray(n_valid, d_real.dtype)
        wdist = (jnp.sum(d_real * mask) - jnp.sum(d_fake * mask)) / nv
        gp = jnp.sum(((gnorm - 1.0) ** 2) * mask) / nv
    loss = -wdist + gp_coef * gp
    return loss, {"wdist": wdist, "gp": gp}


def generator_loss(gp_params, dp, cfg: DcnnConfig, z, gen_fn=None,
                   denom=None):
    """-E[critic(G(z))]; ``denom`` replaces the local mean with a global
    divisor so sharded partial losses sum to the global one."""
    gen = gen_fn if gen_fn is not None else (
        lambda p, z_: generator_apply(p, cfg, z_))
    fake = gen(gp_params, z)
    scores = critic_apply(dp, cfg, fake)
    if denom is None:
        return -jnp.mean(scores)
    return -jnp.sum(scores) / denom


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


class WganTrainer:
    """Bucketed, optionally mesh-sharded WGAN-GP trainer (see module doc).

    ``critic_step(dp, d_state, gp, real, key)`` and
    ``gen_step(gp, g_state, dp, key, batch)`` keep the signatures of the
    old hand-rolled jitted closures; padding, bucketing, sharding and
    per-bucket executable caching all happen behind them."""

    def __init__(self, cfg: DcnnConfig, g_opt, d_opt, *,
                 n_critic: int = 5, gp_coef: float = 10.0,
                 backend: str = "reverse_loop",
                 autotune: bool = True, refine: bool = False,
                 mesh=None, rules=None, z_shards: Optional[int] = None,
                 plan=None):
        if n_critic < 1:
            raise ValueError(
                f"n_critic must be >= 1 (got {n_critic}): the generator "
                "batch is derived from the critic's data batch")
        if backend == "pallas_sparse":
            raise ValueError(
                "pallas_sparse is inference-only: the static zero-skip "
                "plan is derived from frozen weights, which training "
                "updates each step")
        if backend not in ("reverse_loop", "xla", "pallas"):
            raise ValueError(f"unknown training backend {backend!r}")
        self.cfg = cfg
        self.g_opt = g_opt
        self.d_opt = d_opt
        self.n_critic = n_critic
        self.gp_coef = gp_coef
        self.backend = backend
        self._autotune = autotune
        self._refine = refine
        self.mesh = mesh
        if mesh is not None:
            from ..dist.sharding import data_axis_size, make_rules
            self.rules = rules if rules is not None else make_rules("tp")
            self.n_data = data_axis_size(mesh, self.rules)
            if z_shards is not None and z_shards != self.n_data:
                raise ValueError(
                    f"z_shards ({z_shards}) must match the mesh's data "
                    f"extent ({self.n_data}): each device draws one shard")
            self.shards = self.n_data
        else:
            self.rules = rules
            self.n_data = 1
            # z_shards replays the mesh's per-shard key-splitting on one
            # device: trainer(mesh 8-way) == trainer(z_shards=8) exactly
            self.shards = z_shards or 1
        # optional pinned serve-side NetworkPlan: the trainer's bucket
        # whose per-shard sub-batch matches plan.batch runs *exactly* that
        # executable configuration (hash-asserted in _gen_for), so
        # training and serving provably share one plan
        if plan is not None:
            if backend != "pallas":
                raise ValueError(
                    "a pinned NetworkPlan needs backend='pallas' (plans "
                    f"pin the fused serving kernels); got {backend!r}")
            if plan.backend != "pallas" or plan.precision != "fp32":
                raise ValueError(
                    "training consumes fp32 pallas plans; got "
                    f"backend={plan.backend!r} / "
                    f"precision={plan.precision!r}")
            plan.validate_for(cfg)
        self._pinned_plan = plan
        # bucket -> compiled step; trace_counts is the no-retrace probe
        self._critic_fns: Dict[int, Callable] = {}
        self._gen_fns: Dict[int, Callable] = {}
        self._gen_apply: Dict[int, Callable] = {}
        self.trace_counts: Dict[str, Dict[int, int]] = {"critic": {},
                                                        "gen": {}}
        self.tile_choices: Dict[int, Optional[dict]] = {}
        # bucket -> NetworkPlan the generator forward actually runs
        # (pallas backend only) — what plan_fingerprints() reports
        self.plans: Dict[int, Any] = {}

    # -- bucketing ------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest power-of-two >= n (cf. serve.pow2_buckets), rounded up
        to a shard-count multiple so every shard owns an equal sub-batch."""
        if n < 1:
            raise ValueError(f"batch must be >= 1 (got {n})")
        b = 1
        while b < n:
            b <<= 1
        return -(-b // self.shards) * self.shards

    def _local(self, bucket: int) -> int:
        return bucket // self.shards

    # -- generator forward for the loss path ----------------------------
    def _gen_for(self, bucket: int) -> Callable:
        """Per-bucket generator apply: the batch-fused Pallas kernels
        (tiles autotuned against the per-shard sub-batch) with the
        reverse-loop VJP, or the plain differentiable backends."""
        if bucket not in self._gen_apply:
            if self.backend == "pallas":
                from ..plan import build_network_plan
                local = self._local(bucket)
                pinned = self._pinned_plan
                plan = build_network_plan(
                    self.cfg, batch=local, backend="pallas",
                    autotune=self._autotune, refine=self._refine)
                if pinned is not None and plan.batch == pinned.batch:
                    # hash-asserted parity with the serve-side plan: the
                    # bucket that matches the pinned per-device batch must
                    # resolve to the identical executable configuration
                    if plan.stable_hash() != pinned.stable_hash():
                        raise ValueError(
                            f"trainer-built plan for per-shard batch "
                            f"{local} ({plan.stable_hash()}) does not "
                            f"match the pinned serve-side plan "
                            f"({pinned.stable_hash()}); training would "
                            "fill the MXU differently than serving — "
                            "re-pin one side")
                    plan = pinned
                self.plans[bucket] = plan
                self.tile_choices[bucket] = plan.tile_overrides()
                self._gen_apply[bucket] = make_fused_generator(
                    self.cfg, plan=plan)
            else:
                backend = self.backend
                self._gen_apply[bucket] = (
                    lambda p, z, _b=backend: generator_apply(
                        p, self.cfg, z, backend=_b))
        return self._gen_apply[bucket]

    # -- step construction ----------------------------------------------
    def _wrap(self, body, kind: str, bucket: int, n_batch_arg: int):
        """shard_map (mesh) + jit + trace-count probe around a step body.
        ``n_batch_arg`` is the position of the batch-sharded argument."""
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P
            baxes = self.rules.get("batch", "data")
            n_in = body.__code__.co_argcount
            in_specs = tuple(P(baxes) if i == n_batch_arg else P()
                             for i in range(n_in))
            body = jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False)

        def traced(*args):
            counts = self.trace_counts[kind]
            counts[bucket] = counts.get(bucket, 0) + 1
            return body(*args)

        return jax.jit(traced)

    def _replicate(self, *trees):
        """Params and optimizer states, replicated on the mesh (as is).
        A step returns them so placed, and a step whose arguments change
        placement between calls traces again."""
        if self.mesh is None:
            return trees
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(trees, NamedSharding(self.mesh, P()))

    def _psum(self, tree):
        baxes = self.rules.get("batch", "data")
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, baxes), tree)

    def _shard_index(self):
        from ..dist.sharding import shard_index
        return shard_index(self.mesh, self.rules)

    def _critic_shard_terms(self, bucket: int):
        """One shard's sum-based loss/grads: `local` rows starting at
        global row idx*local; divisor = the global valid count."""
        cfg, gp_coef = self.cfg, self.gp_coef
        local = self._local(bucket)
        gen_fn = self._gen_for(bucket)

        def terms(dp, gp, real_l, nv, key, idx):
            kz, kgp = jax.random.split(jax.random.fold_in(key, idx))
            z = jax.random.normal(kz, (local, cfg.z_dim), real_l.dtype)
            rows = idx * local + jnp.arange(local)
            mask = (rows < nv).astype(real_l.dtype)

            def loss_fn(dp_):
                return critic_loss(dp_, gp, cfg, real_l, z, kgp,
                                   gp_coef=gp_coef, mask=mask, n_valid=nv,
                                   gen_fn=gen_fn)

            return jax.value_and_grad(loss_fn, has_aux=True)(dp)

        return terms

    def _build_critic_fn(self, bucket: int) -> Callable:
        terms = self._critic_shard_terms(bucket)
        d_opt = self.d_opt
        local = self._local(bucket)

        if self.mesh is not None:
            def body(dp, d_state, gp, real_l, nv, key):
                (loss, met), grads = terms(dp, gp, real_l, nv, key,
                                           self._shard_index())
                loss, met, grads = self._psum((loss, met, grads))
                dp, d_state = d_opt.update(grads, d_state, dp)
                return dp, d_state, dict(met, d_loss=loss)
        else:
            shards = self.shards

            def body(dp, d_state, gp, real, nv, key):
                acc = None
                for i in range(shards):
                    out = terms(dp, gp, real[i * local:(i + 1) * local],
                                nv, key, i)
                    acc = out if acc is None else _tree_add(acc, out)
                (loss, met), grads = acc
                dp, d_state = d_opt.update(grads, d_state, dp)
                return dp, d_state, dict(met, d_loss=loss)

        return self._wrap(body, "critic", bucket, n_batch_arg=3)

    def _build_gen_fn(self, bucket: int) -> Callable:
        cfg, g_opt = self.cfg, self.g_opt
        local = self._local(bucket)
        gen_fn = self._gen_for(bucket)
        denom = float(bucket)

        def terms(gp, dp, key, idx):
            z = jax.random.normal(jax.random.fold_in(key, idx),
                                  (local, cfg.z_dim), jnp.dtype(cfg.dtype))
            return jax.value_and_grad(generator_loss)(
                gp, dp, cfg, z, gen_fn=gen_fn, denom=denom)

        if self.mesh is not None:
            def body(gp, g_state, dp, key):
                loss, grads = terms(gp, dp, key, self._shard_index())
                loss, grads = self._psum((loss, grads))
                gp, g_state = g_opt.update(grads, g_state, gp)
                return gp, g_state, {"g_loss": loss}
        else:
            shards = self.shards

            def body(gp, g_state, dp, key):
                acc = None
                for i in range(shards):
                    out = terms(gp, dp, key, i)
                    acc = out if acc is None else _tree_add(acc, out)
                loss, grads = acc
                gp, g_state = g_opt.update(grads, g_state, gp)
                return gp, g_state, {"g_loss": loss}

        return self._wrap(body, "gen", bucket, n_batch_arg=-1)

    # -- public steps ----------------------------------------------------
    def critic_step(self, dp, d_state, gp, real, key):
        """One critic update on a (possibly ragged) real batch: pads to
        the bucket, masks the pad rows out of the loss exactly."""
        real = jnp.asarray(real, jnp.dtype(self.cfg.dtype))
        n = real.shape[0]
        bucket = self.bucket_for(n)
        if bucket > n:
            real = jnp.concatenate(
                [real, jnp.zeros((bucket - n,) + real.shape[1:],
                                 real.dtype)], axis=0)
        if bucket not in self._critic_fns:
            self._critic_fns[bucket] = self._build_critic_fn(bucket)
        nv = jnp.asarray(n, jnp.int32)  # dynamic: no retrace per raggedness
        dp, d_state, gp = self._replicate(dp, d_state, gp)
        return self._critic_fns[bucket](dp, d_state, gp, real, nv, key)

    def gen_step(self, gp, g_state, dp, key, batch: int):
        """One generator update; ``batch`` is rounded up to its bucket and
        the z batch drawn at the bucket size (a ragged final data batch
        re-uses the bucket executable instead of compiling a new one)."""
        bucket = self.bucket_for(int(batch))
        if bucket not in self._gen_fns:
            self._gen_fns[bucket] = self._build_gen_fn(bucket)
        gp, g_state, dp = self._replicate(gp, g_state, dp)
        return self._gen_fns[bucket](gp, g_state, dp, key)

    @property
    def total_compiles(self) -> int:
        return sum(v for d in self.trace_counts.values()
                   for v in d.values())

    def plan_fingerprints(self) -> Dict[int, str]:
        """{per-shard batch -> stable hash} of the plans the generator
        forward actually ran (pallas backend) — compare against the serve
        engine's `plans` to prove training and serving pin the same
        executables (`plan.executable_fingerprints` semantics)."""
        from ..plan import executable_fingerprints
        return executable_fingerprints(self.plans.values())

    # -- training loop ----------------------------------------------------
    def init_state(self, key):
        kg, kd = jax.random.split(key)
        gp, _ = generator_init(kg, self.cfg)
        dp, _ = critic_init(kd, self.cfg)
        return gp, dp, self.g_opt.init(gp), self.d_opt.init(dp)

    def fit(self, source, steps: int, key, log_every: int = 50,
            ckpt=None, ckpt_every: int = 200,
            resume_from: Optional[str] = None):
        """Train for (up to) ``steps`` steps.

        ``source`` is either a step-indexed source (anything exposing
        ``batch(step) -> {"images": ...}``, pure in the step — the
        resumable default) or a *streaming batch iterator*: any iterable
        of ``{"images": ...}`` dicts (or bare image arrays).  A streaming
        source is consumed one batch per critic sub-step and training
        stops when it is exhausted — a finite iterator drains exactly,
        with no synthetic batches invented past its end.  Only a
        step-indexed source can replay batches on resume; a resumed
        streaming run continues from wherever its iterator now starts.

        Checkpoints carry generator, critic AND both optimizer states plus
        the step (so a resumed run is bitwise the run that never stopped);
        per-step keys are ``fold_in(key, step)``-derived, which is what
        makes the resumed trajectory identical to the uninterrupted one."""
        kinit, key = jax.random.split(key)
        gp, dp, g_state, d_state = self.init_state(kinit)
        start = 0
        if resume_from is not None:
            from ..ckpt.checkpoint import restore
            tree_like = {"g": gp, "d": dp, "gs": g_state, "ds": d_state}
            tree, step0, extra = restore(resume_from, tree_like)
            if tree is not None:
                gp, dp = tree["g"], tree["d"]
                g_state, d_state = tree["gs"], tree["ds"]
                start = int(extra.get("step", step0)) + 1

        stream = None if hasattr(source, "batch") else iter(source)

        def next_real(step):
            if stream is None:
                return source.batch(step)["images"]
            try:
                rec = next(stream)
            except StopIteration:
                return None
            return rec["images"] if isinstance(rec, dict) else rec

        history: List[dict] = []
        for step in range(start, steps):
            skey = jax.random.fold_in(key, step)
            met: Dict[str, Any] = {}
            batch = None
            for j in range(self.n_critic):
                k = jax.random.fold_in(skey, j)
                real = next_real(step)
                if real is None:
                    # stream drained mid-step: stop before an unpaired
                    # generator update (the step's critic/gen balance
                    # would otherwise silently differ from every other's)
                    return gp, dp, history
                batch = real.shape[0]
                dp, d_state, met_d = self.critic_step(dp, d_state, gp,
                                                      real, k)
                met.update(met_d)
            kg = jax.random.fold_in(skey, self.n_critic)
            gp, g_state, met_g = self.gen_step(gp, g_state, dp, kg, batch)
            met.update(met_g)
            if step % log_every == 0 or step == steps - 1:
                history.append({k: float(v) for k, v in met.items()}
                               | {"step": step})
            if ckpt is not None and step % ckpt_every == 0:
                ckpt.save(step, {"g": gp, "d": dp, "gs": g_state,
                                 "ds": d_state}, extra={"step": step})
        return gp, dp, history


def make_wgan_steps(cfg: DcnnConfig, g_opt, d_opt, mesh=None,
                    backend: str = "reverse_loop", **kwargs):
    """Returns (critic_step, gen_step) with the legacy signatures, now
    bucketed (and mesh-sharded when ``mesh`` is given) via `WganTrainer`.
    The trainer is reachable as ``critic_step.__self__`` for the compile
    probes."""
    trainer = WganTrainer(cfg, g_opt, d_opt, mesh=mesh, backend=backend,
                          **kwargs)
    return trainer.critic_step, trainer.gen_step


def train_wgan(
    cfg: DcnnConfig,
    source,
    steps: int,
    key,
    g_opt,
    d_opt,
    n_critic: int = 5,
    log_every: int = 50,
    ckpt=None,           # optional AsyncCheckpointer
    ckpt_every: int = 200,
    backend: str = "reverse_loop",
    mesh=None,
    resume_from: Optional[str] = None,
):
    trainer = WganTrainer(cfg, g_opt, d_opt, n_critic=n_critic,
                          backend=backend, mesh=mesh)
    return trainer.fit(source, steps, key, log_every=log_every, ckpt=ckpt,
                       ckpt_every=ckpt_every, resume_from=resume_from)
