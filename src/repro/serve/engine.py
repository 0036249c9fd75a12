"""Batched serving engines.

`ServeEngine` (LM path) keeps a fixed batch of sequence slots with
*continuous batching*: a finished sequence frees its slot and a queued
request is admitted into it mid-flight — the in-flight slots keep their
accumulated tokens and continue decoding.  The decode step is a single
compiled function over the whole slot batch.

`DcnnServeEngine` is the paper's own serving path: batched z -> image
generation through a selectable deconvolution backend, run as a real
throughput engine — request batches are padded to a fixed set of
power-of-two *buckets* so the generator compiles once per bucket (never
per request shape), each bucket's tile assignment (including the batch
tile ``t_n``) is resolved against that bucket's batch size, and a
``submit``/``collect`` micro-batching queue coalesces small requests into
the largest fitting bucket."""
from __future__ import annotations

import dataclasses
import re
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.fault import Heartbeat, StragglerMonitor
from ..dist.inject import DeviceLossError, TransientCallError
from ..models.dcnn import DcnnConfig, generator_apply
from ..models.transformer import ModelConfig, apply_lm, init_cache
from ..obs import clock as obsclock
from ..obs import metrics as obsmetrics
from ..obs import trace as obstrace
from .config import EngineConfig
from .errors import (AdmissionRejected, DeadlineExceeded, EngineDegraded,
                     PrecisionUnsupported)
from .sampling import sample


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int
    out: Optional[np.ndarray] = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, batch_size: int,
                 max_len: int, temperature: float = 0.0, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        # scheduler observability (reset per serve() call)
        self.prefill_steps = 0
        self.decode_steps = 0
        self.sample_steps = 0

        def prefill(params, tokens):
            cache = init_cache(cfg, batch_size, max_len)
            logits, cache, _ = apply_lm(params, cfg, tokens, mode="prefill",
                                        cache=cache)
            return logits[:, -1], cache

        def decode(params, cache, tokens):
            logits, cache, _ = apply_lm(params, cfg, tokens, mode="decode",
                                        cache=cache)
            return logits[:, -1], cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 eos_id: int = -1) -> np.ndarray:
        """prompts: (B, S) int32 (B == engine batch).  Static batch path."""
        assert prompts.shape[0] == self.batch
        logits, cache = self._prefill(self.params, jnp.asarray(prompts))
        toks = []
        self.key, k = jax.random.split(self.key)
        nxt = sample(logits, k, self.temperature)
        toks.append(np.asarray(nxt))
        for _ in range(max_new_tokens - 1):
            logits, cache = self._decode(self.params, cache, nxt[:, None])
            self.key, k = jax.random.split(self.key)
            nxt = sample(logits, k, self.temperature)
            toks.append(np.asarray(nxt))
        return np.stack(toks, axis=1)

    # ------------------------------------------------------------------
    # continuous batching: slot scheduler over queued requests
    # ------------------------------------------------------------------
    def serve(self, requests: List[Request]) -> List[Request]:
        """Continuous batching over the fixed slot batch.

        A request is admitted the moment a slot frees — mid-flight, not at
        chunk boundaries — so a long request no longer holds short ones
        hostage (the pre-fix behavior ran static chunks at the chunk-max
        budget).  Admission (re)prefills the *accumulated histories* of
        every active slot, left-padded so all slots share the scalar cache
        position; in-flight slots keep their generated tokens and continue
        from exactly where they were (greedy decoding is bit-identical to
        running each request alone).  Between admissions all slots advance
        through the single compiled decode step.  Each request generates
        exactly its own ``max_new_tokens`` — no slot burns steps on
        another slot's budget.

        Left-pad tokens are ordinary tokens to the (causal, unmasked)
        model — the same property the chunked scheduler already had for
        mixed-length prompts — so a request admitted mid-flight decodes
        the oracle continuation of its *padded* history (pinned by
        tests/test_serve.py::test_continuous_batching_midflight_admission),
        and an admission whose prompt is *longer* than every in-flight
        history re-pads the in-flight slots too, perturbing their
        remaining continuation (in-flight decoding is bit-stable only
        while the slot stays at the longest history).  Each admission also
        re-prefills at a new (batch, s_max) shape, i.e. one XLA compile
        per distinct admission length; length-bucketing the prefill would
        bound that but — without a pad mask — padding is semantics, so it
        stays exact-shape until the model grows pad masking.
        """
        queue = list(requests)
        done: List[Request] = []
        slots: List[Optional[dict]] = [None] * self.batch
        self.prefill_steps = self.decode_steps = self.sample_steps = 0
        nxt = None
        cache = None
        while queue or any(s is not None for s in slots):
            admitted = False
            for i in range(self.batch):
                while slots[i] is None and queue:
                    r = queue.pop(0)
                    if r.max_new_tokens <= 0:
                        # zero-budget request: complete without a slot (the
                        # slot loop tests `left == 0` only after a decrement,
                        # so admitting it would never free the slot)
                        r.out = np.zeros((0,), np.int32)
                        done.append(r)
                        continue
                    slots[i] = {
                        "req": r,
                        "hist": [int(t) for t in np.asarray(r.prompt)],
                        "left": int(r.max_new_tokens),
                        "gen": [],
                    }
                    admitted = True
            if not any(s is not None for s in slots):
                break  # every remaining request was zero-budget
            if admitted:
                # re-prefill the active histories (left-padded: every slot
                # sits at the same cache position, which is what the shared
                # scalar cache["pos"] requires)
                s_max = max(len(s["hist"]) for s in slots if s is not None)
                worst = s_max + max(s["left"] for s in slots
                                    if s is not None)
                assert worst <= self.max_len, (
                    f"history+budget ({worst}) exceeds max_len "
                    f"({self.max_len}); the KV cache would overflow")
                pad = np.zeros((self.batch, s_max), np.int32)
                for i, s in enumerate(slots):
                    if s is not None:
                        pad[i, s_max - len(s["hist"]):] = s["hist"]
                logits, cache = self._prefill(self.params, jnp.asarray(pad))
                self.prefill_steps += 1
            else:
                logits, cache = self._decode(self.params, cache, nxt[:, None])
                self.decode_steps += 1
            self.key, k = jax.random.split(self.key)
            nxt = sample(logits, k, self.temperature)
            self.sample_steps += 1
            nxt_np = np.asarray(nxt)
            for i, s in enumerate(slots):
                if s is None:
                    continue
                tok = int(nxt_np[i])
                s["gen"].append(tok)
                s["hist"].append(tok)
                s["left"] -= 1
                if s["left"] == 0:
                    s["req"].out = np.asarray(s["gen"], np.int32)
                    done.append(s["req"])
                    slots[i] = None   # freed: admitted from queue next step
        return done


def pow2_buckets(max_batch: int) -> Tuple[int, ...]:
    """1, 2, 4, ... up to (and including) max_batch."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    out = [1]
    while out[-1] < max_batch:
        out.append(min(out[-1] * 2, max_batch))
    return tuple(sorted(set(out)))


def shard_aligned_buckets(buckets: Sequence[int], n_shards: int
                          ) -> Tuple[int, ...]:
    """Round every bucket up to a multiple of the data-shard count (so each
    device owns an equal sub-batch) and dedupe.  n_shards=1 is identity."""
    if n_shards <= 1:
        return tuple(sorted(set(int(b) for b in buckets)))
    up = lambda b: -(-int(b) // n_shards) * n_shards
    return tuple(sorted({up(b) for b in buckets}))


class _DeviceClock:
    """When the last bucket call on one set of devices finished.  Every
    engine serving on those devices shares it (`_device_clock`): the
    frontend overlaps waves of its fp32 and int8 engines, and a call that
    queued behind another engine's call starts its occupancy at that
    call's finish, as behind one of its own engine's."""

    __slots__ = ("last_finish",)

    def __init__(self):
        self.last_finish = 0.0


_DEVICE_CLOCKS: Dict[frozenset, _DeviceClock] = {}


def _device_clock(mesh) -> _DeviceClock:
    """The shared `_DeviceClock` of ``mesh``'s devices (the default
    device without a mesh)."""
    devices = frozenset(mesh.devices.flat if mesh is not None
                        else jax.devices()[:1])
    return _DEVICE_CLOCKS.setdefault(devices, _DeviceClock())


class _BucketCall:
    """One launched bucket call: its rows, its images on their way to the
    host, and what its finish books."""

    __slots__ = ("bucket", "take", "chunk", "y", "out", "error", "t0",
                 "seconds", "steady", "retried", "span")

    def __init__(self, bucket: int, take: int, chunk: np.ndarray):
        self.bucket = bucket
        self.take = take          # useful rows; the rest is padding
        self.chunk = chunk        # the padded host rows, for a re-run
        self.y = None             # device images until finished
        self.out: Optional[np.ndarray] = None
        self.error: Optional[Exception] = None
        self.t0 = 0.0             # launch clock
        self.seconds = 0.0        # occupancy, set at finish
        self.steady = True        # did not trace (compile)
        self.retried = False      # needed a transient retry
        self.span = None


class PendingGenerate:
    """A batch `DcnnServeEngine.launch` put on the device.  `result`
    returns its images through the engine's `generate`, which finishes
    its bucket calls in launch order; after it, ``seconds`` is the calls'
    occupancy (the engine's timing samples) and ``retried`` says whether
    any call needed a retry or a re-run."""

    def __init__(self, engine: "DcnnServeEngine", z, rows: int, span):
        self.z = z                # the batch as the caller passed it
        self.rows = rows
        self.span = span
        self.calls: List[_BucketCall] = []
        self.seconds = 0.0
        self.retried = False
        self._engine = engine
        self._images: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None

    def result(self) -> np.ndarray:
        if self._error is not None:
            raise self._error
        if self._images is None:
            try:
                self._images = self._engine._answer(self)
            except BaseException as e:
                self._error = e
                raise
        return self._images


class DcnnServeEngine:
    """The paper's inference workload: batched image generation, served
    through compile-once batch buckets.

    * **Bucketing** — request batches are decomposed by a cost-aware
      chunk plan (`plan_chunks`: padded rows vs per-call overhead), so a
      mixed-size request stream compiles at most ``len(buckets)``
      generator executables — never one per batch shape.
    * **Per-bucket tiles** — for the pallas backends each bucket's tile
      assignment is resolved against that bucket's batch size, letting the
      autotuner pick the batch tile ``t_n`` jointly with the spatial and
      channel tiles (MXU row fill + weight amortization).  Executables are
      built lazily on first use, or eagerly with ``warmup=True`` (which
      also runs one zero-batch through each to pay compile + first-dma
      cost before traffic arrives).
    * **Donated inputs** — on TPU the z buffer is donated to the compiled
      generator, so steady-state serving does not hold two copies of the
      input batch (no-op on CPU, where donation is unimplemented).
    * **Micro-batching queue** — ``submit`` enqueues request rows;
      ``drain`` coalesces everything pending into one generate() over the
      largest fitting buckets; ``collect`` returns a request's images
      (draining on demand).

    * **Mesh sharding** — with ``mesh=`` each bucket's batch is sharded
      along the data axis (`dist.sharding` rules): params are replicated
      via `tree_shardings`, the z batch splits per `batch_pspec`, buckets
      are rounded up to multiples of the device count so every device owns
      an equal sub-batch, and the autotuner resolves tiles (incl. ``t_n``)
      against the *per-device* sub-batch geometry.  ``stats`` /
      ``throughput()`` then report per-device rates.

    * **Quantized serving** — ``precision="int8"`` quantizes the params
      once at construction (self-calibrating on the z ~ N(0,1) serving
      distribution unless a pre-computed ``quant_cfg`` is given) and
      serves every bucket through the int8 batch-fused kernel chain:
      int32 accumulation, fused requant epilogue, activations int8 in
      HBM between layers.  Tiles are autotuned at the int8 dtype (v3
      cache), and the mesh path replicates the quantized tree exactly
      like fp32 params.

    * **Plan/execute** — every bucket serves a pinned `plan.NetworkPlan`
      (tiles, epilogues, quant scales, zero-skip schedules resolved ONCE
      at plan-build time; ``plan_stats`` counts builds and their wall
      clock).  `from_config` accepts a pre-built/deserialized plan so a
      deployment executes exactly the configuration it validated.

    * **Launch / finish** — `launch` uploads each chunk, enqueues its
      step and the device-to-host copy of its images, and returns; the
      `PendingGenerate` it returns finishes the calls in order, through
      `generate`.  A caller that launches the next batch before finishing
      this one overlaps the host's work with the device's; `generate`
      alone launches and finishes at once.

    * **Fault tolerance** — every bucket launch runs guarded: an
      optional `dist.inject.FaultInjector` hook fires scripted faults, a
      transient call failure retries with bounded exponential backoff
      (then fails typed as `EngineDegraded`), an optional
      `dist.fault.Heartbeat` armed while calls are in flight records
      stalls, and a per-bucket `StragglerMonitor` flags steady-state
      calls slower than ``straggler_factor`` x their EMA.  A detected
      **device loss** triggers elastic recovery (`_remesh`): shrink onto
      the surviving prefix via `dist.fault.elastic_mesh`, re-align
      buckets to the new device count, `reshard_tree` the replicated
      params, re-plan every bucket (autotune cache hits via plan hashes
      keep this fast) and ASSERT via `plan.executable_fingerprints` that
      every per-device batch re-derived the validated plan hash — then
      re-run the interrupted chunk and keep serving.  Calls in flight at
      the loss finish first, and a call whose finish raises runs again.
      `submit` takes a per-request deadline; an expired ticket fails typed
      (`DeadlineExceeded`) at drain instead of executing stale work, and
      a drain whose generate() fails restores every ticket to the queue.
      All of it is observable through ``fault_stats``.

    ``trace_counts`` maps bucket -> number of times its generator was
    traced (== compiled); tests pin the no-per-request-recompilation
    guarantee on it."""

    def __init__(self, cfg: DcnnConfig, params, backend: str = "pallas",
                 autotune: bool = True, refine: bool = False,
                 max_batch: int = 64,
                 buckets: Optional[Sequence[int]] = None,
                 warmup: bool = False, donate: bool = True,
                 mesh=None, rules=None, call_overhead_rows: int = 8,
                 precision: str = "fp32", quant_cfg=None,
                 calib_batch: int = 64, calib_seed: int = 0,
                 calib_strategy: str = "mean_ksigma"):
        # deprecation shim (one release): the kwarg sprawl folds into an
        # EngineConfig and routes through the plan-driven setup
        warnings.warn(
            "DcnnServeEngine(cfg, params, **kwargs) is deprecated: build a "
            "serve.EngineConfig and use DcnnServeEngine.from_config(config, "
            "params, plan=...)", DeprecationWarning, stacklevel=2)
        config = EngineConfig(
            model=cfg, backend=backend, precision=precision,
            quant_cfg=quant_cfg, mesh=mesh, rules=rules, autotune=autotune,
            refine=refine, max_batch=max_batch,
            buckets=None if buckets is None else tuple(buckets),
            warmup=warmup, donate=donate,
            call_overhead_rows=call_overhead_rows, calib_batch=calib_batch,
            calib_seed=calib_seed, calib_strategy=calib_strategy)
        self._setup(config, params, None)

    @classmethod
    def from_config(cls, cfg: EngineConfig, params, plan=None,
                    fault_injector=None, metrics=None) -> "DcnnServeEngine":
        """The plan/execute constructor: ``cfg`` is a `serve.EngineConfig`
        and ``plan`` an optional pinned `plan.NetworkPlan` (e.g. loaded
        from JSON) for the bucket whose per-device batch matches
        ``plan.batch`` — remaining buckets plan themselves on first use.
        An int8 plan also supplies the calibration when ``cfg.quant_cfg``
        is None, so a pinned deployment never re-calibrates.
        ``fault_injector`` is an optional `dist.inject.FaultInjector`
        hooked before every bucket dispatch (deterministic fault drills;
        never needed in production).  ``metrics`` is an optional shared
        `obs.MetricsRegistry` — the async frontend passes one registry to
        every per-precision engine so the deployment's series land in one
        place; without it the engine makes its own."""
        self = cls.__new__(cls)
        self._setup(cfg, params, plan, fault_injector, metrics)
        return self

    def _setup(self, config: EngineConfig, params, plan,
               fault_injector=None, metrics=None) -> None:
        from ..workloads import resolve_model, workload_name_for

        # a string model is a registry lookup (typed UnknownWorkloadError
        # on a typo — never a silent fallback); a DcnnConfig passes through
        cfg = resolve_model(config.model)
        self.config = config
        self.cfg = cfg
        self.workload = workload_name_for(cfg)
        self.backend = config.backend
        # chunk-planning knob: one kernel dispatch is costed like computing
        # this many extra rows (trades padded-row waste against call count)
        self.call_overhead_rows = config.call_overhead_rows
        if config.precision not in ("fp32", "int8"):
            raise ValueError(f"unknown precision {config.precision!r}; "
                             "expected 'fp32' or 'int8'")
        if config.precision == "int8" and config.backend != "pallas":
            raise ValueError(
                "precision='int8' runs the dense int8 Pallas kernel; "
                f"backend={config.backend!r} has no quantized variant")
        if config.precision == "int8" and cfg.is_graph:
            raise PrecisionUnsupported(
                f"{cfg.name} is not a chain of deconvolutions (forward "
                "convolutions, BatchNorm or skip joins): it serves at "
                "precision='fp32' only; the int8 chain has no path for it")
        self.precision = config.precision
        self.quant_cfg = config.quant_cfg
        if plan is not None:
            if (plan.backend, plan.precision) != (self.backend,
                                                  self.precision):
                raise ValueError(
                    f"plan was built for backend={plan.backend!r} / "
                    f"precision={plan.precision!r}; the engine config says "
                    f"{self.backend!r} / {self.precision!r}")
            plan.validate_for(cfg)
            # a stale zero-skip schedule (plan pinned, checkpoint since
            # re-pruned) would silently skip nonzero blocks; params are
            # still concrete here, so this is the place to catch it
            plan.verify_sparse_tables(params)
            if self.precision == "int8":
                if self.quant_cfg is None:
                    # serve exactly the calibration the plan pinned
                    self.quant_cfg = plan.quant_config()
                elif plan.quant_config() != self.quant_cfg:
                    # the params would be quantized with one scale set
                    # while the plan's pinned requant epilogues use
                    # another — silently wrong images; fail loudly
                    raise ValueError(
                        "EngineConfig.quant_cfg and the pinned plan carry "
                        "different calibrations; drop one of them (the "
                        "plan's scales are authoritative for its "
                        "executables)")
        if self.precision == "int8":
            from ..quant.calibrate import calibrate, quantize_params
            from ..workloads import calibration_input
            if self.quant_cfg is None:
                # self-calibrate on the serving input distribution — a
                # fixed-seed batch (z ~ N(0,1) latents, or the registered
                # workload's synthesized inputs for image-rooted towers)
                # through the fp32 reference chain, observed by the
                # chosen strategy.  Same (seed, batch) routing as
                # build_network_plan, so scales agree with pinned plans.
                z_cal = calibration_input(cfg, seed=config.calib_seed,
                                          batch=config.calib_batch)
                self.quant_cfg = calibrate(params, cfg, z_cal,
                                           strategy=config.calib_strategy)
            params = quantize_params(params, cfg, self.quant_cfg)
        mesh = config.mesh
        self.mesh = mesh
        if mesh is not None:
            from ..dist.sharding import (data_axis_size, make_rules,
                                         replicated_specs, tree_shardings)
            self.rules = (config.rules if config.rules is not None
                          else make_rules("tp"))
            self.n_devices = data_axis_size(mesh, self.rules)
            # params live replicated on the mesh from the start: steady-state
            # serving never re-transfers them per call
            self._param_shardings = tree_shardings(
                mesh, self.rules, params, replicated_specs(params))
            params = jax.device_put(params, self._param_shardings)
        else:
            self.rules = config.rules
            self.n_devices = 1
            self._param_shardings = None
        self.params = params
        self.buckets = shard_aligned_buckets(
            config.buckets if config.buckets else
            pow2_buckets(config.max_batch), self.n_devices)
        if self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive: {self.buckets}")
        self.max_bucket = self.buckets[-1]
        self._autotune = config.autotune
        self._refine = config.refine
        # donation is a TPU win (steady-state z buffers are reused); on CPU
        # jax warns that donation is unimplemented, so gate on the backend
        self._donate = config.donate and jax.default_backend() == "tpu"
        # typed observability: every legacy dict below (stats, bucket_stats,
        # plan_stats, fault_stats) keeps its exact shape for existing
        # callers AND dual-writes the shared registry at the same sites,
        # labeled (net, precision[, bucket]) so one registry can hold a
        # whole multi-engine deployment.  Spans go to the process tracer
        # (no-ops unless obs.trace.enable() ran).
        self.metrics = (metrics if metrics is not None
                        else obsmetrics.MetricsRegistry())
        self._tracer = obstrace.get_tracer()
        self._mlabels = {"net": cfg.name, "workload": self.workload,
                         "precision": self.precision}
        self._m_dispatch = self.metrics.histogram(
            "engine.dispatch_seconds",
            "healthy steady-state dispatch wall clock (Table II samples)")
        self._m_plan_build = self.metrics.histogram(
            "engine.plan_build_seconds", "NetworkPlan build wall clock")
        self._m_tainted = self.metrics.counter(
            "engine.tainted_calls",
            "steady dispatches excluded from Table II (transient retries)")
        self._m_fault = self.metrics.counter(
            "engine.fault_events", "fault-path events by kind (label: event)")
        self._m_generate_calls = self.metrics.counter(
            "engine.generate_calls", "generate() invocations")
        self._m_images = self.metrics.counter(
            "engine.images", "useful (unpadded) images generated")
        self._m_padded = self.metrics.counter(
            "engine.padded_images", "padded rows burned on bucket alignment")
        self._m_upload_bytes = self.metrics.counter(
            "engine.upload_bytes",
            "host-to-device bytes of the bucket calls' padded rows")
        self._m_copy_back_bytes = self.metrics.counter(
            "engine.copy_back_bytes",
            "device-to-host bytes of the bucket calls' images")
        self._m_tap_folded = self.metrics.counter(
            "engine.tap_folded_layers",
            "deconvolution layers of each bucket's plan that the dense "
            "kernel runs tap-folded, counted at the plan's build")
        self._m_devices = self.metrics.gauge(
            "engine.device_count", "devices serving this engine")
        self._m_devices.set(self.n_devices, **self._mlabels)
        self._fns: Dict[int, Callable] = {}
        self.plans: Dict[int, object] = {}
        self.tile_choices: Dict[int, Optional[dict]] = {}
        self.trace_counts: Dict[int, int] = {}
        self._sparse_plan_memo: Dict[tuple, tuple] = {}
        # queue entries are (ticket, rows, absolute deadline or None).
        # _qlock guards the queue state (submit/collect/shed may run from
        # concurrent caller threads under the async frontend); _drain_lock
        # serializes drains so two threads never run generate() on the
        # same engine at once; _inflight names tickets a drain has taken
        # off the queue but not yet resolved, so a concurrent collect
        # waits for that drain instead of misreporting "already
        # collected".
        self._qlock = threading.Lock()
        self._drain_lock = threading.Lock()
        self._inflight: Set[int] = set()
        self._pending: List[Tuple[int, np.ndarray, Optional[float]]] = []
        self._results: Dict[int, np.ndarray] = {}
        self._failures: Dict[int, Exception] = {}
        self._next_id = 0
        self.stats = {"generate_calls": 0, "images": 0, "padded_images": 0,
                      "device_count": self.n_devices}
        # fault-tolerance machinery: injector hook, per-bucket straggler
        # monitors over the steady-state call timings, optional stall
        # heartbeat, and the observable event counters the bench reports
        self.fault_injector = fault_injector
        self._stragglers: Dict[int, StragglerMonitor] = {}
        self._dispatches = 0
        # launched bucket calls not yet finished, in launch order (the
        # dispatching thread alone touches them), and the last finish on
        # these devices, by any engine (each call's occupancy starts no
        # earlier)
        self._launched: List[_BucketCall] = []
        self._clock = _device_clock(self.mesh)
        # launched batches not yet answered, by the id of the array the
        # caller passed (each pending holds that array), oldest first
        self._launched_batches: Dict[int, List[PendingGenerate]] = {}
        self.fault_stats = {
            "retries": 0, "transient_failures": 0, "stragglers": 0,
            "heartbeat_fires": 0, "deadline_expired": 0, "shed": 0,
            "finish_failures": 0, "remesh_events": [],
        }
        self._heartbeat = None
        if config.heartbeat_timeout_s is not None:
            self._heartbeat = Heartbeat(config.heartbeat_timeout_s,
                                        self._on_stall)
            self._heartbeat.disarm()   # armed while a call is in flight
        # plan-build observability: serving must pay planning once per
        # bucket, never per call (bench pins this)
        self.plan_stats = {"builds": 0, "build_seconds": 0.0}
        if plan is not None:
            # static DRC before anything compiles: a pinned plan that
            # drifted from the code (stale tiles, broken requant chain,
            # foreign mesh) is rejected here with the rule-by-rule
            # report, not discovered as a mid-serve crash.  Weight-digest
            # checking already happened via verify_sparse_tables above.
            from ..analysis.check.plan_drc import check_network_plan
            check_network_plan(
                plan, n_devices=self.n_devices,
                buckets=self.buckets).raise_if_failed()
            seeded = [b for b in self.buckets
                      if self.shard_batch(b) == plan.batch]
            if not seeded:
                raise ValueError(
                    f"plan.batch={plan.batch} matches no bucket's "
                    f"per-device batch (buckets={self.buckets}, "
                    f"{self.n_devices} devices)")
            for b in seeded:
                self.plans[b] = plan
        # per-bucket serving observability: wall-clock + image counters so
        # the engine *learns* throughput (global and per-device) per bucket
        self.bucket_stats: Dict[int, Dict[str, float]] = {}
        if config.warmup:
            for b in self.buckets:
                self._warmup_bucket(b)

    # -- per-bucket executable construction ----------------------------
    def shard_batch(self, bucket: int) -> int:
        """The batch one device actually runs for a bucket (== the bucket
        on a single device); tile choices are fitted to this, not to the
        global bucket."""
        return bucket // self.n_devices

    def _plan_for(self, bucket: int):
        """The bucket's pinned `NetworkPlan`, built on first use.

        Planning — autotune cache interaction, quant-scale wiring,
        zero-skip schedule construction (memoized across buckets sharing
        channel tiles) — happens exactly once per bucket; `generate`
        executes the pinned plan with zero per-call re-planning."""
        if bucket not in self.plans:
            from ..plan import build_network_plan

            t0 = obsclock.now()
            self.plans[bucket] = build_network_plan(
                self.cfg,
                batch=self.shard_batch(bucket),
                backend=self.backend,
                precision=self.precision,
                params=self.params,
                quant_cfg=self.quant_cfg,
                autotune=self._autotune,
                refine=self._refine,
                sparse_table_cache=self._sparse_plan_memo,
            )
            dt = obsclock.now() - t0
            folded = self.plans[bucket].tap_folded_layers()
            self.plan_stats["builds"] += 1
            self.plan_stats["build_seconds"] += dt
            self._m_plan_build.observe(dt, bucket=bucket, **self._mlabels)
            self._m_tap_folded.inc(folded, bucket=bucket, **self._mlabels)
            self._tracer.complete(f"plan_build b{bucket}", t0, t0 + dt,
                                  cat="engine", bucket=bucket,
                                  tap_folded=folded, **self._mlabels)
        return self.plans[bucket]

    def _get_fn(self, bucket: int) -> Callable:
        if bucket not in self._fns:
            plan = self._plan_for(bucket)
            self.tile_choices[bucket] = plan.tile_overrides()

            if self.precision == "int8":
                from ..quant.infer import quantized_generator_apply

                def apply(p, z, _plan=plan):
                    return quantized_generator_apply(
                        p, self.cfg, self.quant_cfg, z, plan=_plan)
            else:
                def apply(p, z, _plan=plan):
                    return generator_apply(p, self.cfg, z, plan=_plan)

            if self.mesh is not None:
                # SPMD: every device runs the same per-shard executable on
                # its bucket/n_devices rows (the tiles above were fitted to
                # exactly that sub-batch).  check_vma=False: pallas_call has
                # no replication rule.
                from jax.sharding import NamedSharding, PartitionSpec as P

                from ..dist.sharding import batch_pspec

                baxes = self.rules.get("batch", "data")
                apply = jax.shard_map(apply, mesh=self.mesh,
                                      in_specs=(P(), P(baxes)),
                                      out_specs=P(baxes), check_vma=False)
                z_sh = NamedSharding(
                    self.mesh, batch_pspec(self.mesh, self.rules, bucket, 2))
                img_sh = NamedSharding(
                    self.mesh, batch_pspec(self.mesh, self.rules, bucket, 4))
                shardings = dict(
                    in_shardings=(self._param_shardings, z_sh),
                    out_shardings=img_sh)
            else:
                shardings = {}

            def fn(p, z, _b=bucket, _apply=apply):
                # tracing happens exactly once per compilation: the counter
                # is the no-per-request-recompilation acceptance probe
                self.trace_counts[_b] = self.trace_counts.get(_b, 0) + 1
                # rows arrive flat (`_upload`); a latent row already is
                return _apply(p, z.reshape(z.shape[:1] + self.cfg.input_shape))

            # the step's stable name in profiles and HLO (jit_<name>)
            fn.__name__ = fn.__qualname__ = re.sub(
                r"\W", "_", f"serve_{self.cfg.name}_{self.precision}"
                f"_b{bucket}")
            self._fns[bucket] = jax.jit(
                fn, **shardings,
                **(dict(donate_argnums=(1,)) if self._donate else {}))
        return self._fns[bucket]

    def _warmup_bucket(self, bucket: int) -> None:
        fn = self._get_fn(bucket)
        z = np.zeros((bucket,) + self.cfg.input_shape, self.cfg.dtype)
        jax.block_until_ready(fn(self.params, self._upload(z)))

    @staticmethod
    def _upload(chunk: np.ndarray) -> jax.Array:
        """``chunk`` on the device, each row flat: the TPU lays an image
        row's (H, W, C) out with its 3 colour channels major, a transpose
        of every value on the host (7.4 ms for 16 rows of 256x256x3
        against 2.1 ms flat, and 75,000 profiler events against 47:
        PERF.md); the step restores the row's shape on the device."""
        return jnp.asarray(chunk.reshape(len(chunk), -1))

    # -- guarded dispatch + elastic recovery ---------------------------
    def _on_stall(self) -> None:
        # heartbeat callback: a dispatched call has been silent past the
        # configured timeout.  Record it (the Heartbeat catches callback
        # errors, but there is nothing to raise into — the stalled call
        # owns the thread).  This runs on the watcher thread, so the
        # counter bump takes _qlock like every other fault_stats write.
        with self._qlock:
            self.fault_stats["heartbeat_fires"] += 1
        self._m_fault.inc(event="heartbeat_fires", **self._mlabels)
        self._tracer.instant("heartbeat_fire", cat="fault", **self._mlabels)

    def close(self) -> None:
        """Release the stall-watcher thread (no-op without a heartbeat)."""
        if self._heartbeat is not None:
            self._heartbeat.close()

    def _launch(self, bucket: int, take: int, chunk: np.ndarray,
                gen_span) -> "_BucketCall":
        """Launch one guarded bucket call: injector hook, heartbeat armed,
        bounded retry-with-backoff on transient failures.  The call
        uploads ``chunk``, enqueues the step and then the device-to-host
        copy of its images, and returns without waiting for either
        (`_finish` collects them).

        `TransientCallError` is retried up to ``max_retries`` times then
        raised as `EngineDegraded`; `DeviceLossError` escapes to
        `_launch_rows`, which remeshes.  With the tracer on, each attempt
        is a ``dispatch b<bucket>`` span (`_traced_launch`) under
        ``gen_span``; off, the launch is three expressions."""
        fn = self._get_fn(bucket)
        call = _BucketCall(bucket, take, chunk)
        attempts = self.config.max_retries + 1
        for attempt in range(attempts):
            if self._heartbeat is not None:
                self._heartbeat.arm()
            traces_before = self.trace_counts.get(bucket, 0)
            try:
                # the injector hook sits inside the timed window: an
                # injected SlowCall is a slow *dispatch*, visible to the
                # straggler monitor exactly like a real one
                call.t0 = obsclock.now()
                if self._tracer.enabled:
                    self._traced_launch(fn, call, attempt, gen_span)
                else:
                    if self.fault_injector is not None:
                        self.fault_injector.before_call(bucket)
                    call.y = fn(self.params, self._upload(chunk))
                    call.y.copy_to_host_async()
            except TransientCallError as e:
                self._rest_heartbeat()
                with self._qlock:
                    self.fault_stats["transient_failures"] += 1
                self._m_fault.inc(event="transient_failures", **self._mlabels)
                self._tracer.instant("transient_failure", cat="fault",
                                     bucket=bucket, attempt=attempt,
                                     **self._mlabels)
                if attempt + 1 >= attempts:
                    raise EngineDegraded(
                        f"bucket-{bucket} call failed {attempts} "
                        "time(s); retries exhausted") from e
                with self._qlock:
                    self.fault_stats["retries"] += 1
                self._m_fault.inc(event="retries", **self._mlabels)
                self._tracer.instant("retry", cat="fault", bucket=bucket,
                                     attempt=attempt, **self._mlabels)
                time.sleep(self.config.retry_backoff_s * (2 ** attempt))
                continue
            except BaseException:
                self._rest_heartbeat()
                raise
            call.steady = self.trace_counts.get(bucket, 0) == traces_before
            call.retried = attempt > 0
            self._m_upload_bytes.inc(chunk.nbytes, **self._mlabels)
            self._launched.append(call)
            return call

    def _traced_launch(self, fn, call: "_BucketCall", attempt: int,
                       gen_span) -> None:
        """One launch attempt as the start of a ``dispatch b<bucket>``
        span whose parent is ``gen_span`` (the ``generate`` span): the
        injector hook, then ``dispatch.upload`` (host side of the
        host-to-device copy) and ``dispatch.call`` (until the jitted call
        and the device-to-host copy are enqueued).  `_traced_finish` ends
        it."""
        tr = self._tracer
        span = tr.begin(f"dispatch b{call.bucket}", cat="engine",
                        parent=getattr(gen_span, "id", None), annotate=True,
                        bucket=call.bucket, **self._mlabels)
        try:
            if self.fault_injector is not None:
                self.fault_injector.before_call(call.bucket)
            with tr.span("dispatch.upload", cat="engine", parent=span.id,
                         bytes=call.chunk.nbytes):
                x = self._upload(call.chunk)
            with tr.span("dispatch.call", cat="engine", parent=span.id):
                call.y = fn(self.params, x)
                call.y.copy_to_host_async()
        except BaseException as e:
            tr.end(span, error=type(e).__name__, retried=attempt > 0)
            raise
        call.span = span

    def _finish(self, call: "_BucketCall") -> None:
        """Bring a launched call's images to the host (``call.out``) or
        keep what that raised (``call.error``), and book the call.

        Its ``seconds`` is its own occupancy: from the later of its
        launch and the previous call's finish on these devices (by any
        engine), to its own finish, so a call that queued behind its
        predecessor is not a straggler."""
        try:
            if call.span is not None:
                self._traced_finish(call)
            else:
                call.out = np.asarray(call.y)
        except Exception as e:
            call.error = e
        finally:
            call.y = None
            self._launched.remove(call)
            t1 = obsclock.now()
            call.seconds = t1 - max(call.t0, self._clock.last_finish)
            self._clock.last_finish = t1
            if self._launched and self._heartbeat is not None:
                self._heartbeat.arm()     # progress: a fresh silence window
            else:
                self._rest_heartbeat()
        if call.error is not None:
            with self._qlock:
                self.fault_stats["finish_failures"] += 1
            self._m_fault.inc(event="finish_failures", **self._mlabels)
            self._tracer.instant("finish_failure", cat="fault",
                                 bucket=call.bucket,
                                 error=type(call.error).__name__,
                                 **self._mlabels)
            return
        self._book(call)

    def _traced_finish(self, call: "_BucketCall") -> None:
        """The end of a call's ``dispatch b<bucket>`` span:
        ``dispatch.wait`` (until the device is done) and
        ``dispatch.copy_back`` (until its images are on the host); the
        wait costs one more host wake-up."""
        tr = self._tracer
        span = call.span
        try:
            with tr.span("dispatch.wait", cat="engine", parent=span.id):
                y = jax.block_until_ready(call.y)
            with tr.span("dispatch.copy_back", cat="engine",
                         parent=span.id) as phase:
                call.out = np.asarray(y)
                phase.set(bytes=call.out.nbytes)
        except BaseException as e:
            tr.end(span, error=type(e).__name__, retried=call.retried)
            raise
        tr.end(span, steady=call.steady, retried=call.retried)

    def _rest_heartbeat(self) -> None:
        """Disarm the heartbeat once no call is in flight: an idle queue
        is not a stall."""
        if self._heartbeat is not None and not self._launched:
            self._heartbeat.disarm()

    def _abandon(self, call: "_BucketCall") -> None:
        """Drop a launched call whose answer nobody will read."""
        if call in self._launched:
            self._launched.remove(call)
            call.y = None
            self._tracer.end(call.span, abandoned=True)
            self._rest_heartbeat()

    def _settle(self) -> None:
        """Finish every call in flight, in launch order (before a remesh:
        their answers and samples belong to the mesh that ran them)."""
        for call in list(self._launched):
            self._finish(call)

    def _book(self, call: "_BucketCall") -> None:
        """Per-call accounting of a finished call: straggler monitor,
        padded rows, and the steady timing samples (Table II)."""
        bucket, dt = call.bucket, call.seconds
        self._m_copy_back_bytes.inc(call.out.nbytes, **self._mlabels)
        self._dispatches += 1
        pad = bucket - call.take
        if pad:
            self.stats["padded_images"] += pad
            self._m_padded.inc(pad, **self._mlabels)
        if not call.steady:
            # a call that traced (compiled) would poison the learned rates
            # by orders of magnitude
            return
        if not call.retried:
            # a dispatch that needed retries is not a healthy sample: it
            # must not seed the straggler baseline either
            mon = self._stragglers.setdefault(
                bucket, StragglerMonitor(
                    factor=self.config.straggler_factor,
                    warmup_steps=self.config.straggler_warmup))
            if mon.observe(self._dispatches, dt):
                with self._qlock:
                    self.fault_stats["stragglers"] += 1
                self._m_fault.inc(event="stragglers", **self._mlabels)
                self._tracer.instant("straggler", cat="fault",
                                     bucket=bucket, seconds=dt,
                                     **self._mlabels)
        bs = self.bucket_stats.setdefault(
            bucket, {"calls": 0, "images": 0, "seconds": 0.0,
                     "sumsq_seconds": 0.0, "tainted_calls": 0,
                     "tainted_seconds": 0.0})
        if call.retried:
            # outcome-tagged: a dispatch that needed transient retries is
            # real work but not a healthy run — its wall clock stays out
            # of the Table II mean/std/CV samples (which are *run-to-run
            # variation of the healthy path*, the paper's predictability
            # claim)
            bs["tainted_calls"] += 1
            bs["tainted_seconds"] += dt
            self._m_tainted.inc(bucket=bucket, **self._mlabels)
        else:
            bs["calls"] += 1
            bs["images"] += call.take
            # running first/second moments of the per-call wall clock
            # (the paper's Table II mean/std methodology) — O(1) state,
            # not a per-call sample list a long-lived engine would grow
            # without bound
            bs["seconds"] += dt
            bs["sumsq_seconds"] += dt * dt
            self._m_dispatch.observe(dt, bucket=bucket, **self._mlabels)

    def _remesh(self, keep: int) -> None:
        """Elastic recovery from device loss: shrink onto the surviving
        ``keep``-device prefix, re-align the bucket set to the new
        device count, reshard the (replicated) params, and re-plan every
        bucket — recording `plan.executable_fingerprints` before/after
        so "same plan for the same per-device batch" is ASSERTED, not
        assumed.  A hash mismatch means the rebuilt executables are not
        the ones that were validated, and the engine refuses to serve
        them."""
        if self.mesh is None or not self.config.elastic:
            raise EngineDegraded(
                "device loss without an elastic mesh: nothing to shrink "
                "onto (serve with mesh=... and elastic=True)")
        from ..dist.fault import elastic_mesh, reshard_tree
        from ..dist.sharding import (data_axis_size, replicated_specs,
                                     tree_shardings)
        from ..plan import executable_fingerprints

        t0 = obsclock.now()
        devs = list(self.mesh.devices.flat)
        if not 1 <= keep <= len(devs):
            raise EngineDegraded(
                f"cannot remesh: {keep} survivor(s) of {len(devs)} "
                "device(s)")
        before = executable_fingerprints(self.plans.values())
        devices_before = self.n_devices
        self.mesh = elastic_mesh(
            devs[:keep], model_parallel=self.mesh.shape.get("model", 1))
        self.n_devices = data_axis_size(self.mesh, self.rules)
        self._clock = _device_clock(self.mesh)
        self._param_shardings = tree_shardings(
            self.mesh, self.rules, self.params,
            replicated_specs(self.params))
        self.params = reshard_tree(self.params, self._param_shardings)
        self.buckets = shard_aligned_buckets(
            self.config.buckets if self.config.buckets
            else pow2_buckets(self.config.max_batch), self.n_devices)
        self.max_bucket = self.buckets[-1]
        # stale executables/plans/tiles were fitted to the old device
        # count; re-plan everything up front (recovery pays it once)
        self._fns.clear()
        self.tile_choices.clear()
        self._stragglers.clear()
        self.plans = {}
        for b in self.buckets:
            self._plan_for(b)
        after = executable_fingerprints(self.plans.values())
        matches = {sb: after[sb] == h for sb, h in before.items()
                   if sb in after}
        self.stats["device_count"] = self.n_devices
        # timing samples from the pre-loss mesh describe a capacity that
        # no longer exists: mixing them into post-loss rates/CV would
        # report a throughput nobody can have.  Snapshot them into the
        # remesh event (observability) and start the accounting fresh.
        stats_before = {b: dict(s) for b, s in self.bucket_stats.items()}
        self.bucket_stats = {}
        event = {
            "bucket_stats_before": stats_before,
            "devices_before": devices_before,
            "devices_after": self.n_devices,
            "buckets": list(self.buckets),
            "plan_hashes_before": before,
            "plan_hashes_after": after,
            "plan_hash_matches": matches,
            "seconds": obsclock.now() - t0,
        }
        with self._qlock:
            self.fault_stats["remesh_events"].append(event)
        self._m_fault.inc(event="remesh_events", **self._mlabels)
        self._m_devices.set(self.n_devices, **self._mlabels)
        self._tracer.instant("remesh", cat="fault",
                             devices_before=devices_before,
                             devices_after=self.n_devices,
                             seconds=event["seconds"], **self._mlabels)
        if not all(matches.values()):
            raise EngineDegraded(
                f"post-remesh plan hash mismatch {matches}: the "
                "shrunken mesh did not re-derive the validated "
                "executables")

    def bucket_for(self, n: int) -> int:
        """Smallest bucket covering n requests (largest bucket if n exceeds
        them all — the caller then chunks)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_bucket

    def plan_chunks(self, n: int) -> List[Tuple[int, int]]:
        """Chunk plan for an n-row batch: ``[(take, bucket), ...]`` with
        ``sum(take) == n``.

        Full max-bucket chunks are sliced first; the sub-max tail is then
        planned *cost-aware*: at each level the smallest covering bucket
        (one padded call) competes with slicing the largest exact-fitting
        bucket and recursing, costed as computed rows plus
        ``call_overhead_rows`` per kernel dispatch.  So a 36-row tail at
        buckets 1..64 runs 32+4 (the pre-fix loop ran one 64-row call —
        28 padded rows), while a 63-row tail stays one padded 64-call
        instead of fragmenting into six row-starved small-bucket calls."""
        if n < 0:
            raise ValueError(f"negative batch: {n}")
        plan: List[Tuple[int, int]] = []
        remaining = n
        while remaining >= self.max_bucket:
            plan.append((self.max_bucket, self.max_bucket))
            remaining -= self.max_bucket
        plan.extend(self._plan_tail(remaining))
        return plan

    def _plan_cost(self, plan: List[Tuple[int, int]]) -> int:
        return sum(b for _, b in plan) + self.call_overhead_rows * len(plan)

    def _plan_tail(self, r: int) -> List[Tuple[int, int]]:
        """Cost-aware plan for a tail below the largest bucket (recursion
        depth is bounded by len(buckets): each slice at least halves what
        the remaining buckets can cover)."""
        if r == 0:
            return []
        cover = self.bucket_for(r)
        best = [(r, cover)] if cover >= r else None
        fit = [b for b in self.buckets if b <= r]
        if fit:
            b = max(fit)
            cand = [(b, b)] + self._plan_tail(r - b)
            if best is None or self._plan_cost(cand) < self._plan_cost(best):
                best = cand
        assert best is not None, (r, self.buckets)
        return best

    # -- launch / finish -------------------------------------------------
    def launch(self, z: np.ndarray,
               parent: Optional[int] = None) -> "PendingGenerate":
        """Launch every bucket call of ``z`` (B, z_dim), for ANY B:
        chunked/padded to the bucket set via `plan_chunks`, so no batch
        size ever triggers a recompile.  Returns at once, with each
        call's step and device-to-host copy enqueued;
        `PendingGenerate.result` finishes them in order.  ``parent`` is
        the id of the span the ``generate`` span names as its parent, if
        any.

        A caller may launch the next batch before finishing this one, so
        the host's upload of one overlaps the device's step and copy
        back of the other; calls finish in launch order.

        Fault path: a transient failure retries at launch (`_launch`); a
        detected device loss finishes whatever is in flight, remeshes
        onto the survivors (`_remesh`), then re-plans the rows not yet
        launched against the post-loss bucket set; a call whose finish
        raises runs its rows again through the guarded path."""
        pending = self._launch_pending(z, parent)
        self._launched_batches.setdefault(id(z), []).append(pending)
        return pending

    def generate(self, z: np.ndarray) -> np.ndarray:
        """z: (B, z_dim) for ANY B; its images, once every bucket call is
        back.  A batch `launch` put on the device (this very array) is
        finished, oldest launch first; any other is launched and
        finished now.  Every answer the engine hands out, launched or
        not, passes through here."""
        queued = self._launched_batches.get(id(z))
        if queued:
            pending = queued.pop(0)
            if not queued:
                del self._launched_batches[id(z)]
        else:
            pending = self._launch_pending(z, None)
        return self._finish_pending(pending)

    def _answer(self, pending: "PendingGenerate") -> np.ndarray:
        """``pending``'s images through `generate`: its batch array is
        put first among the launches of that array.  A `generate` that
        answers without finishing it (one that computes other arrays)
        leaves it launched: its calls are then abandoned."""
        queued = self._launched_batches.get(id(pending.z), [])
        if pending in queued:
            queued.remove(pending)
            queued.insert(0, pending)
        try:
            return self.generate(pending.z)
        finally:
            if pending in queued:
                queued.remove(pending)
                if not queued:
                    self._launched_batches.pop(id(pending.z), None)
                for c in pending.calls:
                    self._abandon(c)
                self._tracer.end(pending.span, abandoned=True)

    def _launch_pending(self, z, parent: Optional[int]) -> "PendingGenerate":
        rows = np.asarray(z, dtype=self.cfg.dtype)
        tr = self._tracer
        span = (tr.begin("generate", cat="engine", parent=parent,
                         annotate=True, rows=rows.shape[0], **self._mlabels)
                if tr.enabled else None)
        pending = PendingGenerate(self, z, rows.shape[0], span)
        try:
            pending.calls = self._launch_rows(rows, span)
        except BaseException as e:
            tr.end(span, error=type(e).__name__)
            raise
        return pending

    def _launch_rows(self, z: np.ndarray, span) -> List["_BucketCall"]:
        """Launch the chunk plan of ``z``; a device loss settles what is
        in flight, remeshes, and re-plans the rows not yet launched."""
        n = z.shape[0]
        calls: List[_BucketCall] = []
        i = 0
        chunks = self.plan_chunks(n)
        try:
            while chunks:
                take, bucket = chunks[0]
                chunk = z[i:i + take]
                pad = bucket - take
                if pad:
                    chunk = np.concatenate(
                        [chunk, np.zeros((pad,) + z.shape[1:], z.dtype)],
                        axis=0)
                try:
                    call = self._launch(bucket, take, chunk, span)
                except DeviceLossError as e:
                    self._settle()
                    self._remesh(e.keep)
                    chunks = self.plan_chunks(n - i)
                    continue
                chunks.pop(0)
                calls.append(call)
                i += take
        except BaseException:
            for call in calls:
                self._abandon(call)
            raise
        return calls

    def _collect(self, call: "_BucketCall", pending: "PendingGenerate"
                 ) -> np.ndarray:
        """A call's useful rows, finishing it if it is still in flight; a
        call whose finish raised runs its rows again, once, through the
        guarded path."""
        if call.out is None and call.error is None:
            self._finish(call)
        if call.error is None:
            pending.seconds += call.seconds
            return call.out[:call.take]
        pending.retried = True
        redo = self._launch_rows(call.chunk[:call.take], pending.span)
        outs = []
        try:
            for c in redo:
                if c.out is None and c.error is None:
                    self._finish(c)
                if c.error is not None:
                    raise c.error
                pending.seconds += c.seconds
                outs.append(c.out[:c.take])
        except BaseException:
            for c in redo:
                self._abandon(c)
            raise
        return np.concatenate(outs, axis=0) if len(outs) != 1 else outs[0]

    def _finish_pending(self, pending: "PendingGenerate") -> np.ndarray:
        tr = self._tracer
        try:
            outs = [self._collect(c, pending) for c in pending.calls]
        except BaseException as e:
            for c in pending.calls:
                self._abandon(c)
            tr.end(pending.span, error=type(e).__name__)
            raise
        n = pending.rows
        pending.retried = pending.retried or any(
            c.retried for c in pending.calls)
        self.stats["generate_calls"] += 1
        self.stats["images"] += n
        self._m_generate_calls.inc(**self._mlabels)
        self._m_images.inc(n, **self._mlabels)
        tr.end(pending.span)
        return (np.concatenate(outs, axis=0) if len(outs) != 1
                else outs[0])

    def throughput(self) -> Dict[int, Dict[str, float]]:
        """Learned per-bucket *steady-state* serving rates (compiling
        calls are excluded from the timers): useful images/s overall and
        per device (the mesh analogue of the paper's per-PE utilization),
        plus run-to-run variation — mean, std and CV (std/mean) of the
        per-call wall clock over repeated calls, the paper's Table II
        methodology already used by `benchmarks.common.time_fn`.

        Samples are outcome-tagged: only *healthy* dispatches (no
        transient-failure retries, same mesh) feed the mean/std/CV;
        retried dispatches surface as ``tainted_calls`` /
        ``tainted_seconds`` alongside, and a device-loss remesh resets
        the accounting entirely (the pre-loss snapshot lives in the
        remesh event)."""
        out = {}
        for bucket, bs in self.bucket_stats.items():
            if bs["seconds"] <= 0.0:
                continue
            rate = bs["images"] / bs["seconds"]
            mean_s = bs["seconds"] / bs["calls"]
            var = max(0.0, bs["sumsq_seconds"] / bs["calls"] - mean_s ** 2)
            std_s = var ** 0.5
            out[bucket] = {
                "img_per_s": rate,
                "img_per_s_per_device": rate / self.n_devices,
                "calls": bs["calls"],
                "mean_s": mean_s,
                "std_s": std_s,
                "cv": std_s / max(mean_s, 1e-12),
                "tainted_calls": bs.get("tainted_calls", 0),
                "tainted_seconds": bs.get("tainted_seconds", 0.0),
            }
        return out

    def service_estimate(self, bucket: int) -> Optional[float]:
        """Best current estimate of one steady dispatch's wall clock for
        ``bucket``: the per-bucket `StragglerMonitor` EMA when it has
        observations (tracks drift, ignores outliers), else the healthy
        mean from ``bucket_stats``, else None (no data yet).  This is the
        capacity signal the SLO frontend's admission control and
        deadline-aware scheduler run on."""
        mon = self._stragglers.get(bucket)
        if mon is not None and mon.estimate() is not None:
            return mon.estimate()
        bs = self.bucket_stats.get(bucket)
        if bs and bs["calls"] > 0:
            return bs["seconds"] / bs["calls"]
        return None

    # -- micro-batching queue --------------------------------------------
    def submit(self, z: np.ndarray,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue a request of one or more z rows; returns a ticket id.

        ``deadline_s`` (default: `EngineConfig.default_deadline_s`)
        bounds how long the ticket may wait in the queue: a drain that
        reaches it past the deadline fails it with `DeadlineExceeded`
        instead of executing stale work (`collect` raises the typed
        error).  Thread-safe: concurrent submitters get distinct
        tickets."""
        z = np.asarray(z, dtype=self.cfg.dtype)
        if z.ndim == len(self.cfg.input_shape):
            z = z[None]
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        deadline = (None if deadline_s is None
                    else obsclock.now() + deadline_s)
        with self._qlock:
            rid = self._next_id
            self._next_id += 1
            self._pending.append((rid, z, deadline))
        return rid

    def shed(self, rid: int, reason: str = "") -> bool:
        """Remove a still-pending ticket from the queue and fail it typed
        (`AdmissionRejected`) — the backpressure lever: load-shedding a
        ticket that will not make its deadline must resolve it, never
        silently drop it (a dropped ticket is a caller blocked forever).
        Returns False if the ticket is no longer pending (already
        draining, resolved, or never issued)."""
        with self._qlock:
            for i, (t, _, _) in enumerate(self._pending):
                if t == rid:
                    del self._pending[i]
                    self.fault_stats["shed"] += 1
                    self._failures[rid] = AdmissionRejected(
                        reason or f"ticket {rid} shed before execution",
                        stage="shed")
                    self._m_fault.inc(event="shed", **self._mlabels)
                    self._tracer.instant("shed", cat="fault", rid=rid,
                                         **self._mlabels)
                    return True
        return False

    def drain(self) -> None:
        """Run everything pending as one coalesced stream: all queued rows
        are concatenated and generated through the cost-aware
        `plan_chunks`, so ten 3-image requests run as a few large-bucket
        calls, not ten bucket-4 calls.

        Failure semantics: a ticket whose deadline already passed fails
        typed (`DeadlineExceeded`, raised at `collect`) without being
        executed, and if the coalesced generate() itself fails, every
        drained ticket is RESTORED to the queue before the error
        propagates — a fault mid-generate must not silently drop the
        queue (the pre-fix behavior lost every queued request).

        Thread-safe: drains are serialized (two threads never run
        generate() on one engine concurrently) and in-flight tickets are
        tracked so a concurrent `collect` waits for the owning drain
        instead of misreporting the ticket as already collected."""
        with self._drain_lock:
            self._drain_locked()

    def _drain_locked(self) -> None:
        with self._qlock:
            if not self._pending:
                return
            reqs, self._pending = self._pending, []
            live = []
            now = obsclock.now()
            for rid, z, deadline in reqs:
                if deadline is not None and now > deadline:
                    self.fault_stats["deadline_expired"] += 1
                    self._failures[rid] = DeadlineExceeded(
                        f"ticket {rid} missed its deadline by "
                        f"{now - deadline:.3f}s before execution")
                    self._m_fault.inc(event="deadline_expired",
                                      **self._mlabels)
                    self._tracer.instant("deadline_expired", cat="fault",
                                         rid=rid, **self._mlabels)
                else:
                    live.append((rid, z, deadline))
                    self._inflight.add(rid)
        if not live:
            return
        rows = np.concatenate([z for _, z, _ in live], axis=0)
        try:
            imgs = self.generate(rows)
        except Exception:
            with self._qlock:
                self._pending = live + self._pending
                self._inflight.difference_update(r for r, _, _ in live)
            raise
        with self._qlock:
            ofs = 0
            for rid, z, _ in live:
                self._results[rid] = imgs[ofs:ofs + len(z)]
                ofs += len(z)
                self._inflight.discard(rid)

    def collect(self, rid: int,
                timeout_s: Optional[float] = None) -> np.ndarray:
        """Images for ticket ``rid`` (drains the queue if still pending).

        Raises the ticket's typed failure (e.g. `DeadlineExceeded`,
        `AdmissionRejected`) if it failed, and a KeyError that
        distinguishes a ticket this engine never issued from one whose
        result was already handed out.

        ``timeout_s`` bounds the wait end-to-end: a ticket that cannot
        resolve in time — another thread's drain still owns it, or its
        dispatch was shed / lost mid-remesh and nothing will ever
        deliver it — raises `DeadlineExceeded` at expiry instead of
        blocking forever (the pre-fix behavior for a vanished ticket was
        an unbounded wait under concurrent draining)."""
        deadline = (None if timeout_s is None
                    else obsclock.now() + timeout_s)

        def expired() -> bool:
            return deadline is not None and obsclock.now() >= deadline

        while True:
            with self._qlock:
                if rid in self._failures:
                    raise self._failures.pop(rid)
                if rid in self._results:
                    return self._results.pop(rid)
                pending = any(t == rid for t, _, _ in self._pending)
                inflight = rid in self._inflight
                issued = 0 <= rid < self._next_id
            if not issued:
                raise KeyError(f"unknown ticket {rid}: this engine never "
                               "issued it")
            if pending:
                # drive the queue ourselves; honor the timeout while
                # waiting for another thread's drain to release the lock
                if deadline is None:
                    self.drain()
                    continue
                remaining = deadline - obsclock.now()
                if remaining <= 0 or not self._drain_lock.acquire(
                        timeout=remaining):
                    raise DeadlineExceeded(
                        f"ticket {rid} still pending after "
                        f"{timeout_s:.3f}s (queue busy)")
                try:
                    self._drain_locked()
                finally:
                    self._drain_lock.release()
                continue
            if inflight:
                # another thread's drain owns it: it will resolve (or be
                # restored to pending) when that drain finishes
                if expired():
                    raise DeadlineExceeded(
                        f"ticket {rid} still in flight after "
                        f"{timeout_s:.3f}s")
                time.sleep(0.001)
                continue
            # issued, but neither pending, in flight, nor resolved
            if deadline is None:
                raise KeyError(
                    f"ticket {rid} was already collected (results are "
                    "handed out exactly once)")
            if expired():
                raise DeadlineExceeded(
                    f"ticket {rid} did not resolve within {timeout_s:.3f}s "
                    "(dispatch shed or lost mid-remesh)")
            time.sleep(0.001)

    @property
    def total_compiles(self) -> int:
        return sum(self.trace_counts.values())
