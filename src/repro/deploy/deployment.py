"""``Deployment`` — the single lifecycle object from programming to
drift-aware serving.

The paper's device-lifetime story as first-class operations:

* ``Deployment.program(cfg, key, backend=...)`` — the programming event.
  Owns the resident base (uint8 conductance codes for every RRAM leaf;
  read back to floats for the ``dequant`` backend), the ``RramConfig``
  and the substrate backend binding.
* ``dep.advance(hours)`` — the drift clock: time passes in the field and
  the resident codes re-drift (``rram.apply_drift`` with the log-time
  sigma), WITHOUT reprogramming. Deterministic per event index and keyed
  off the deployment key, so any drift history replays exactly.
* ``dep.calibrate(batch_or_samples)`` — feature-KD calibration of the
  SRAM side-cars (teacher-feature caching + ``CalibState`` + the jitted
  step loop); returns a ``CalibrationReport``. The array is never
  written.
* ``dep.serve()`` — a ``ServeSession`` with the DoRA magnitudes merged
  (Algorithm 2 line 12) and the backend scope bound.
* ``dep.snapshot()`` / ``Deployment.restore()`` — persistence through
  ``checkpoint.CheckpointManager``: adapters + optimizer + the lifecycle
  record (keys + drift history). The multi-GB base is never stored — it
  is re-derived by replaying program + drift events.

Because drift can now happen repeatedly, the multi-drift-epoch scenario
(program -> advance -> calibrate -> advance -> recalibrate -> serve) is a
plain sequence of method calls — the one-shot free-function API could
not represent it.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import substrate
from repro.checkpoint.manager import as_manager
from repro.core import rram
from repro.core.calibrate import (
    CalibState,
    calibrated_fraction,
    drift_model,
    jit_calib_step,
    make_cached_calib_step,
    make_calib_step,
    merge_adapters_for_serve,
    program_model,
    rram_bytes,
    sram_bytes,
    teacher_features_program,
)
from repro.data.pipeline import DataConfig, global_batch_at_step
from repro.deploy import serving
from repro.models import transformer as T
from repro.optim.adam import AdamW, adamw_init

Pytree = Any

_DEPLOYMENT_META = "deployment.json"


def _key_pair(key) -> Tuple[jax.Array, jax.Array]:
    """(teacher_init_key, programming_key) from an int seed, a PRNGKey,
    or an explicit pair. Int seeds use (PRNGKey(s), PRNGKey(s+1)) — the
    exact keying of the legacy ``serve.load_student`` path, which is what
    makes shim-vs-Deployment parity bitwise."""
    if isinstance(key, (tuple, list)):
        tk, pk = key
        return jnp.asarray(tk), jnp.asarray(pk)
    if isinstance(key, (int, np.integer)):
        return jax.random.PRNGKey(key), jax.random.PRNGKey(key + 1)
    key = jnp.asarray(key)
    return key, jax.random.fold_in(key, 1)


def _dequant_like(codes: Pytree, like: Pytree) -> Pytree:
    """Read a codes-resident tree back to floats, leaf dtypes taken from
    ``like`` (the pre-programming base). Bitwise identical to
    ``program_model(mode='dequant')`` for the same keys — it is the same
    ``dequantize`` applied to the same codes. Non-RRAM leaves pass
    through as the SAME buffers (teacher/student share peripherals)."""

    def leaf(c, w):
        if isinstance(c, rram.CrossbarWeight):
            return rram.dequantize(c, dtype=w.dtype)
        return c

    return jax.tree_util.tree_map(
        leaf, codes, like,
        is_leaf=lambda n: isinstance(n, rram.CrossbarWeight),
    )


def _device_batch(np_batch: Dict) -> Dict:
    return {
        k: jnp.asarray(v, jnp.bfloat16 if v.dtype == np.float32 else None)
        for k, v in np_batch.items()
    }


def calibration_batch(cfg, batch_or_samples, seq_len: int) -> Dict:
    """The deterministic calibration batch for ``cfg``: a batch dict
    passes through verbatim; an int is a calibration-set size (paper: 10
    samples) generated by the data pipeline at step 0. Shared by
    ``Deployment.calibrate`` and the fleet's batched calibration — the
    same arguments always produce the same batch, which is what makes
    fleet-vs-per-chip parity bitwise."""
    if isinstance(batch_or_samples, dict):
        return batch_or_samples
    n = int(batch_or_samples)
    dcfg = DataConfig(
        vocab=cfg.vocab, seq_len=seq_len, global_batch=n,
        n_calibration_samples=n,
        enc_src_len=seq_len if cfg.encoder_layers else 0,
        d_model=cfg.d_model if (cfg.encoder_layers or cfg.vision_tokens)
        else 0,
        vision_tokens=cfg.vision_tokens,
    )
    return _device_batch(global_batch_at_step(dcfg, 0))


@dataclasses.dataclass
class CalibrationReport:
    """Outcome of one ``Deployment.calibrate`` call.

    JSON-serializable (``to_dict``/``from_dict`` round-trip exactly):
    the calibration registry persists reports verbatim in artifact
    metadata sidecars, so ``initial_loss``/``final_loss`` are stored
    fields rather than re-derived from the loss trace."""

    losses: List[float]          # per-step feature MSE (Algorithm 1 loss)
    epochs_run: int
    sram_bytes: int              # resident side-car bytes (digital SRAM)
    rram_bytes: int              # resident base bytes (analog array)
    base_params: int
    adapter_params: int
    calibrated_fraction: float   # paper's 2.34% headline
    backend: str
    drift_events: int            # drift-clock ticks seen before this calib
    initial_loss: float = float("nan")
    final_loss: float = float("nan")
    warm_started: bool = False   # adapters seeded from a registry reference
    warm_source: Optional[str] = None  # the seeding artifact ("key@vN")

    def __post_init__(self):
        # derive from the trace when a caller didn't pass them (legacy
        # construction sites predate the stored fields)
        if self.losses and np.isnan(self.initial_loss):
            self.initial_loss = float(self.losses[0])
        if self.losses and np.isnan(self.final_loss):
            self.final_loss = float(self.losses[-1])

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "CalibrationReport":
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, payload: str) -> "CalibrationReport":
        return cls.from_dict(json.loads(payload))

    def summary(self) -> str:
        return (
            f"calibrated {self.epochs_run} epochs: feature MSE "
            f"{self.initial_loss:.6f} -> {self.final_loss:.6f} | "
            f"sram_bytes={self.sram_bytes} "
            f"({self.calibrated_fraction:.2%} of params) "
            f"rram_bytes={self.rram_bytes} backend={self.backend}"
        )


class Deployment:
    """One RRAM deployment over its lifetime. See module docstring.

    The resident uint8 codes (``self.codes``) are the ground truth for
    the array state; ``self.base`` is what forwards consume — the codes
    themselves under ``codes``/``codes_adc`` backends, or the float
    read-back under ``dequant``. ``advance`` mutates only the codes (and
    refreshes the read-back); ``calibrate`` mutates only the adapters.
    """

    def __init__(
        self, cfg, backend: str, teacher_base: Pytree, codes: Pytree,
        adapters: Pytree, teacher_key: jax.Array, program_key: jax.Array,
    ):
        if backend not in serving.BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; available: {serving.BACKENDS}"
            )
        self.cfg = cfg
        self.backend = backend
        self.teacher_base = teacher_base
        self.codes = codes
        self.adapters = adapters
        self.teacher_key = teacher_key
        self.program_key = program_key
        self.opt_state: Optional[Pytree] = None
        self.step: int = 0
        self.drift_hours: List[float] = []
        self.fault_specs: List[Any] = []
        self._fault_map = None
        self._refresh_base()

    # -- programming event --------------------------------------------------

    @classmethod
    def program(
        cls, cfg, key=0, *, backend: str = "dequant",
        adapters: Optional[Pytree] = None,
    ) -> "Deployment":
        """The deployment event: init the teacher, program every RRAM
        leaf onto the simulated crossbar (one programming event, incl.
        programming-time drift), and bind the substrate backend.

        ``key`` is an int seed, a PRNGKey, or a ``(teacher_key,
        program_key)`` pair. ``adapters`` seeds the SRAM side-cars
        (default: fresh DoRA adapters from the teacher init)."""
        teacher_key, program_key = _key_pair(key)
        params = T.init_params(teacher_key, cfg)
        codes = program_model(params["base"], cfg.rram, program_key, mode="codes")
        return cls(
            cfg=cfg, backend=backend, teacher_base=params["base"], codes=codes,
            adapters=params["adapters"] if adapters is None else adapters,
            teacher_key=teacher_key, program_key=program_key,
        )

    def _refresh_base(self):
        # self.codes stays pristine (the drift clock's ground truth);
        # consumers read the faulty view, re-derived here after every
        # programming / drift / injection event — which is what keeps
        # stuck cells pinned through drift and makes repeat injection
        # of the same fault a no-op.
        self.codes_view = substrate.faulted_codes(
            self.codes, self._fault_map, self.cfg.rram
        )
        if self.backend == "dequant":
            self.base = _dequant_like(self.codes_view, self.teacher_base)
        else:
            self.base = self.codes_view

    # -- drift clock --------------------------------------------------------

    @property
    def field_hours(self) -> float:
        """Total field time elapsed on the drift clock."""
        return float(sum(self.drift_hours))

    def advance(self, hours: float) -> "Deployment":
        """Let ``hours`` of field time pass: the resident codes re-drift
        (log-time relaxation; each tick draws the variance increment over
        the cumulative clock, so tick granularity doesn't change the
        total drift) without any reprogramming. Event ``i`` draws from
        ``fold_in(leaf_key, i)`` — deterministic, order-sensitive, and
        exactly replayable from ``(program_key, drift_hours)``.

        ``hours=0`` is a true no-op: no field time elapsed, so no drift
        event is recorded (it used to append a zero-hour event that
        consumed an event index). Negative hours are rejected — the
        drift clock cannot run backwards."""
        hours = float(hours)
        if hours < 0:
            raise ValueError(
                f"drift clock cannot run backwards (hours={hours})"
            )
        if hours == 0.0:
            return self
        self.codes = drift_model(
            self.codes, self.cfg.rram, self.program_key,
            hours=hours, event_index=len(self.drift_hours),
            clock_offset=self.field_hours,
        )
        self.drift_hours.append(float(hours))
        self._refresh_base()
        return self

    # -- fault injection ----------------------------------------------------

    def inject(self, faults) -> "Deployment":
        """Inject device faults (a ``FaultSpec`` or a sequence): a
        lifecycle event like drift, recorded for snapshot/restore
        replay. The pristine codes are untouched — the composite
        ``FaultMap`` is re-materialized from ALL recorded specs and
        re-applied at read-back (``_refresh_base``), so injection is
        idempotent (re-injecting an identical spec changes nothing: map
        composition is a lattice join) and stuck cells stay pinned
        through subsequent ``advance`` ticks."""
        specs = list(faults) if isinstance(faults, (list, tuple)) else [faults]
        self.fault_specs.extend(specs)
        self._rebuild_fault_map()
        self._refresh_base()
        return self

    def _rebuild_fault_map(self):
        from repro.faults import build_map, compose_maps

        self._fault_map = compose_maps(
            build_map(self.codes, spec, self.cfg.rram)
            for spec in self.fault_specs
        ) if self.fault_specs else None

    # -- calibration --------------------------------------------------------

    def calib_state(self) -> CalibState:
        """The whole-model calibration state over this deployment's
        resident base (used directly by the production train driver;
        ``adopt`` syncs the result back)."""
        if self.opt_state is None:
            self.opt_state = adamw_init(self.adapters)
        return CalibState(
            self.teacher_base, self.base, self.adapters, self.opt_state,
            jnp.asarray(self.step, jnp.int32),
        )

    def adopt(self, state: CalibState) -> "Deployment":
        """Sync adapters/optimizer/step back from an externally-run
        ``CalibState`` loop (launch/train.py's mesh/checkpoint loop)."""
        self.adapters = state.adapters
        self.opt_state = state.opt_state
        self.step = int(state.step)
        return self

    def _calibration_batch(self, batch_or_samples, seq_len: int) -> Dict:
        return calibration_batch(self.cfg, batch_or_samples, seq_len)

    def calibrate(
        self, batch_or_samples: Union[Dict, int] = 10, *,
        steps: int = 20, lr: float = 1e-3, opt: Optional[AdamW] = None,
        seq_len: int = 32, cached_teacher: Optional[bool] = None,
        loss_threshold: float = 0.0, registry=None,
        warm_start: bool = False, record: bool = True,
    ) -> CalibrationReport:
        """Algorithm 1 over the whole model: train ONLY the SRAM
        side-cars against the frozen teacher, on the current (possibly
        multiply-drifted) resident base. ``batch_or_samples`` is a batch
        dict or a calibration-set size (paper: 10 samples, generated
        deterministically). Teacher features are cached once per call
        (enc-dec and VLM configs cache encoder features and the vision
        prefix alongside the decoder chain); codes-resident bases
        execute through the differentiable ``dequant`` backend — the
        codes stay frozen either way. The compiled programs (teacher
        forward and step) are built once per ``(cfg, opt, backend)`` and
        reused across calls (``core.calibrate``'s registry); the teacher
        features and every step are computed afresh in each call.

        Registry threading (``repro.registry``): with ``registry=`` set,
        the run is persisted as a versioned artifact keyed by this
        deployment's ``(cfg, backend, drift signature)`` and stability-
        checked against the key's promoted reference (``record=False``
        skips persisting); with ``warm_start=True`` the adapters AND
        optimizer are first seeded from the nearest stable reference
        instead of the zero-initialized side-cars (falling back to cold
        when the registry has nothing usable)."""
        import contextlib

        cfg = self.cfg
        opt = opt if opt is not None else AdamW(lr=lr)
        backend_ctx = (
            substrate.use_backend("dequant")
            if self.backend != "dequant" else contextlib.nullcontext()
        )
        losses: List[float] = []
        # ``calibrate.prepare`` runs from entry until the first step has
        # been dispatched; each ``calibrate.step`` holds one step's loss
        # read (and, after the first, its call)
        with backend_ctx:
            with jax.profiler.TraceAnnotation("calibrate.prepare"):
                warm_rec = None
                if registry is not None and warm_start:
                    from repro.registry.warmstart import seed_deployment

                    warm_rec = seed_deployment(self, registry)
                batch = self._calibration_batch(batch_or_samples, seq_len)
                use_cached = (True if cached_teacher is None
                              else bool(cached_teacher))
                state = self.calib_state()
                if use_cached:
                    feats = teacher_features_program(cfg)(
                        self.teacher_base, batch)
                    step_fn = jit_calib_step(make_cached_calib_step(cfg, opt),
                                             key=("cached", cfg, opt))
                    run = lambda s: step_fn(s, feats, batch)
                else:
                    step_fn = jit_calib_step(make_calib_step(cfg, opt),
                                             key=("fused", cfg, opt))
                    run = lambda s: step_fn(s, batch)
                if steps > 0:
                    state, metrics = run(state)
            for i in range(steps):
                with jax.profiler.TraceAnnotation("calibrate.step"):
                    if i:
                        state, metrics = run(state)
                    losses.append(float(metrics["loss"]))
                if loss_threshold and losses[-1] <= loss_threshold:
                    break
        self.adopt(state)
        n_base, n_adapters = T.count_params(
            {"base": self.base, "adapters": self.adapters}
        )
        report = CalibrationReport(
            losses=losses, epochs_run=len(losses),
            sram_bytes=sram_bytes(self.adapters),
            rram_bytes=rram_bytes(self.base),
            base_params=n_base, adapter_params=n_adapters,
            calibrated_fraction=n_adapters / max(n_base, 1),
            backend=self.backend, drift_events=len(self.drift_hours),
            warm_started=warm_rec is not None,
            warm_source=None if warm_rec is None else warm_rec.name,
        )
        if registry is not None and record:
            registry.record(
                self.cfg, self.backend, self.drift_signature(),
                adapters=self.adapters, opt_state=self.opt_state,
                report=report,
            )
        return report

    def drift_signature(self) -> np.ndarray:
        """This deployment's registry signature: the device feature
        (programming key) plus its cumulative drift/fault state — what
        calibration artifacts are keyed by and warm-start lookups rank
        distance against (``registry/warmstart.drift_signature``)."""
        from repro.registry.warmstart import drift_signature

        return drift_signature(
            self.cfg.rram, self.program_key,
            field_hours=self.field_hours,
            drift_events=len(self.drift_hours),
            fault_events=len(self.fault_specs),
        )

    def reset_adapters(self) -> "Deployment":
        """Discard the SRAM side-cars back to the fresh (output-
        preserving) teacher init and clear the optimizer — the
        "calibrate from scratch" state a new process starts from.
        Deterministic: re-derives the same init ``program`` produced.
        The resident codes and the drift clock are untouched."""
        self.adapters = T.init_params(self.teacher_key, self.cfg)["adapters"]
        self.opt_state = None
        self.step = 0
        return self

    # -- serving ------------------------------------------------------------

    def serve(self, *, accum: str = "f32", mesh=None) -> serving.ServeSession:
        """Bind for serving: merge the DoRA magnitudes (Algorithm 2 line
        12 — no per-step norm recompute) and return a session with the
        substrate backend scope attached.

        Operand-padding cache: under the ``codes`` backend the session
        params are a PREPARED tree (``substrate/prepared.py``) — every
        static kernel operand (codes, per-column scale, low-rank
        factors, merged gamma) is padded to the serve tile alignment
        once here, same-input leaves (gate+up, fused QKV) are fused
        into single launches, and with ``accum="int8"`` the s8 recodes
        are baked in too. Per decode step the only padding left inside
        jit is the activation ``x`` itself; this hoist is what removes
        the per-call ``jnp.pad`` copies the old path issued for every
        static operand on every launch. The deployment's own trees
        (``self.base``/``self.adapters``) stay unprepared — drift,
        calibration and byte accounting keep operating on raw per-leaf
        codes.

        ``accum`` selects the kernel accumulation path ("f32" | "int8")
        for the codes backend; other backends ignore it.

        ``mesh`` binds the session tensor-parallel: column-shardable
        prepared leaves are wrapped + placed with ``NamedSharding`` over
        the mesh's "model" axis and the decode/prefill steps run under
        ``shard_map`` (bitwise-matching the single-device session —
        sharding.rules/prepared.py own the wrap policy). Codes backend
        only; ``session.reshard`` re-binds after elastic degradation.
        """
        if accum not in ("f32", "int8"):
            raise ValueError(f"accum must be 'f32' or 'int8', got {accum!r}")
        if mesh is not None and self.backend != "codes":
            raise ValueError(
                f"mesh serving runs the prepared codes fast path; "
                f"backend={self.backend!r} is single-device"
            )
        merged = merge_adapters_for_serve(self.base, self.adapters)
        base = self.base
        options = {}
        if self.backend == "codes":
            base = substrate.prepare_base_for_serve(
                self.base, merged, self.cfg, int8=(accum == "int8")
            )
            options["accum"] = accum
        return serving.ServeSession(
            self, {"base": base, "adapters": merged}, options=options,
            mesh=mesh,
        )

    # -- introspection ------------------------------------------------------

    def rram_bytes(self) -> int:
        return rram_bytes(self.base)

    def sram_bytes(self) -> int:
        return sram_bytes(self.adapters)

    def calibrated_fraction(self) -> float:
        return calibrated_fraction(self.base, self.adapters)

    def _teacher_logits(self, batch: Dict) -> jax.Array:
        # The teacher is frozen, so repeated logit_mse calls on the same
        # batch (quickstart tracks the gap across the whole lifecycle)
        # reuse one forward; the cache holds the batch leaves so object
        # identity is a sound key.
        leaves = tuple(jax.tree_util.tree_leaves(batch))
        cached = getattr(self, "_teacher_logits_cache", None)
        if cached is not None and len(cached[0]) == len(leaves) and all(
            a is b for a, b in zip(cached[0], leaves)
        ):
            return cached[1]
        t = T.forward(
            {"base": self.teacher_base, "adapters": {}}, batch, self.cfg,
            use_adapters=False,
        ).astype(jnp.float32)
        self._teacher_logits_cache = (leaves, t)
        return t

    def logit_mse(self, batch: Dict, *, use_adapters: bool = True) -> float:
        """Teacher/student logit MSE on ``batch`` — the drift-degradation
        / calibration-recovery metric the examples report."""
        t = self._teacher_logits(batch)
        with serving.backend_scope(self.backend, self.cfg):
            s = T.forward(
                {"base": self.base,
                 "adapters": self.adapters if use_adapters else {}},
                batch, self.cfg, use_adapters=use_adapters,
            ).astype(jnp.float32)
        return float(jnp.mean((t - s) ** 2))

    # -- persistence --------------------------------------------------------

    def snapshot(
        self, directory_or_manager, *, blocking: bool = True
    ) -> int:
        """Checkpoint the mutable lifecycle state through
        ``CheckpointManager`` (atomic, retained, optionally async — the
        same path ``runtime/fault.PreemptionGuard`` shutdowns use):
        adapters + optimizer + the lifecycle record (keys, drift
        history). The base is NOT stored — restore re-derives it by
        replaying the programming event and every drift tick."""
        manager = as_manager(directory_or_manager)
        if self.opt_state is None:
            self.opt_state = adamw_init(self.adapters)
        step = int(self.step)
        lifecycle = {
            "teacher_key": np.asarray(self.teacher_key),
            "program_key": np.asarray(self.program_key),
            "drift_hours": np.asarray(self.drift_hours, np.float64),
        }
        manager.save(
            step,
            {"adapters": self.adapters, "opt": self.opt_state,
             "lifecycle": lifecycle},
            blocking=blocking,
        )
        meta = {
            "format": 1, "backend": self.backend,
            "arch": getattr(self.cfg, "name", None),
            "drift_events": len(self.drift_hours),
            "fault_events": [spec.to_dict() for spec in self.fault_specs],
        }
        with open(os.path.join(manager.directory, _DEPLOYMENT_META), "w") as f:
            json.dump(meta, f)
        return step

    @classmethod
    def restore(
        cls, cfg, directory, *, step: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> "Deployment":
        """Rebuild a deployment from a snapshot directory: re-program
        from the recorded keys, replay the drift history tick-by-tick
        (deterministic — the restored codes are bitwise the codes at
        snapshot time), then load adapters + optimizer. ``backend``
        overrides the recorded binding (e.g. restore a dequant-trained
        deployment straight onto the fused codes path)."""
        manager = as_manager(directory)
        if step is None:
            step = manager.latest_step()
        if step is None:
            raise FileNotFoundError(f"no snapshots in {directory}")
        meta_path = os.path.join(manager.directory, _DEPLOYMENT_META)
        meta = {}
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
        backend = backend or meta.get("backend", "dequant")
        life = manager.restore(
            step,
            {"lifecycle": {
                "teacher_key": np.zeros((2,), np.uint32),
                "program_key": np.zeros((2,), np.uint32),
                "drift_hours": np.zeros((0,), np.float64),
            }},
        )["lifecycle"]
        dep = cls.program(
            cfg, (life["teacher_key"], life["program_key"]), backend=backend
        )
        for hours in np.asarray(life["drift_hours"]).tolist():
            if hours == 0.0:
                # legacy snapshots recorded zero-hour events (advance(0)
                # used to append one, consuming an event index); replay
                # the index consumption so later ticks draw the same keys
                dep.drift_hours.append(0.0)
            else:
                dep.advance(hours)
        fault_events = meta.get("fault_events") or []
        if fault_events:
            # fault replay commutes with drift replay (faults never touch
            # the pristine codes; the view is re-derived), so injecting
            # after the full drift history reproduces the snapshot bitwise
            from repro.faults import FaultSpec

            dep.inject([FaultSpec.from_dict(d) for d in fault_events])
        restored = manager.restore(
            step, {"adapters": dep.adapters, "opt": adamw_init(dep.adapters)}
        )
        dep.adapters = restored["adapters"]
        dep.opt_state = restored["opt"]
        dep.step = int(step)
        return dep


# ---------------------------------------------------------------------------
# Abstract (eval_shape) views — the dry-run/compile planner builds its
# sharded CalibState and merged-adapter serve params from these, so the
# planning path and the live path construct deployments the same way.
# ---------------------------------------------------------------------------


def abstract_params(cfg) -> Pytree:
    return jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))


def abstract_calib_state(cfg, params_abs: Optional[Pytree] = None) -> CalibState:
    params_abs = abstract_params(cfg) if params_abs is None else params_abs
    opt_abs = jax.eval_shape(adamw_init, params_abs["adapters"])
    return CalibState(
        params_abs["base"], params_abs["base"], params_abs["adapters"],
        opt_abs, jax.ShapeDtypeStruct((), jnp.int32),
    )


def abstract_serve_params(cfg, params_abs: Optional[Pytree] = None) -> Dict:
    params_abs = abstract_params(cfg) if params_abs is None else params_abs
    merged_abs = jax.eval_shape(
        merge_adapters_for_serve, params_abs["base"], params_abs["adapters"]
    )
    return {"base": params_abs["base"], "adapters": merged_abs}
