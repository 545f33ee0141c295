"""``Fleet`` — N chips, one model, batched pytrees.

The paper's deployment story at scale: every edge device carries the
SAME target weights but its own programming noise and its own
conductance-drift trajectory, and each must be restored with a tiny
per-chip SRAM adapter rather than RRAM rewrites. ``Deployment`` models
one such chip; ``Fleet`` models N of them as *batched* pytrees with a
leading chip axis — not N Python-level deployments:

* ``Fleet.program(cfg, key, n_chips)`` — ONE stacked programming event:
  every RRAM leaf becomes a ``CrossbarWeight`` with a leading ``(N,
  ...)`` chip axis (``jax.vmap`` of ``calibrate.program_leaf`` over
  per-chip keys ``fold_in(program_key, chip)``), while digital
  peripherals (norms, embeddings) stay SHARED buffers. Bitwise
  identical per chip to N ``Deployment.program`` calls with the same
  keys.
* ``fleet.advance(hours, chips=...)`` — heterogeneous drift clocks:
  each chip keeps its own ordered event history; a tick re-drifts all
  affected chips in one vmapped dispatch over per-chip ``(key, sigma,
  event_index)``. Order-independent ACROSS chips (each chip's draws
  depend only on its own key and history), order-sensitive within one.
* ``fleet.calibrate(...)`` — ONE ``jax.vmap``-ed DoRA loop over
  ``make_cached_calib_step``: the teacher-feature cache is computed once
  and amortized across the whole fleet (calibrating 64 chips costs one
  teacher trace), and the jitted step compiles ONCE per fleet shape —
  zero per-chip retraces (``fleet_compile_count`` pins this).
* ``fleet.chip(i)`` / ``fleet.serve(i)`` — slice chip ``i`` back out as
  a plain ``Deployment`` (bitwise: views of the stacked state). Serving
  reuses the per-``(cfg, backend)`` compiled-step registry, so serving
  chip 47 after chip 0 compiles nothing.
* ``fleet.snapshot()`` / ``Fleet.restore()`` — the multi-GB stacked
  base is never stored; restore replays the programming event and every
  per-chip drift tick (round-robin over heterogeneous histories) to
  bitwise equality.

The drift-aware recalibration policy over a fleet lives in
``fleet/scheduler.py`` (``RecalibrationScheduler`` + ``FleetReport``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import substrate
from repro.checkpoint.manager import as_manager
from repro.core import calibrate as C
from repro.core import rram
from repro.core.calibrate import (
    CalibState,
    jit_calib_step,
    make_cached_calib_loss,
    make_cached_calib_step,
    make_calib_step,
    rram_bytes,
    sram_bytes,
    teacher_features,
)
from repro.deploy.deployment import (
    Deployment,
    _dequant_like,
    _key_pair,
    calibration_batch,
)
from repro.deploy import serving
from repro.models import transformer as T
from repro.optim.adam import AdamW, adamw_init, adamw_update
from repro.optim.compress import allreduce_compressed

Pytree = Any

_FLEET_META = "fleet.json"


# ---------------------------------------------------------------------------
# stacked-pytree helpers: RRAM leaves carry the chip axis, peripherals are
# shared buffers — the same split program_model draws between RRAM and
# digital leaves.
# ---------------------------------------------------------------------------


def _is_cw(n) -> bool:
    return isinstance(n, rram.CrossbarWeight)


def chip_axes(tree: Pytree) -> Pytree:
    """Per-leaf vmap axis spec for a fleet-stacked base tree: ``0`` for
    RRAM leaves (``CrossbarWeight`` or their float read-backs), ``None``
    for shared digital peripherals. Usable as a ``jax.vmap``
    in/out_axes prefix."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: 0 if (_is_cw(x) or C._is_rram_leaf(p)) else None,
        tree, is_leaf=_is_cw,
    )


def _take(tree: Pytree, idx) -> Pytree:
    """Gather chips ``idx`` (int array -> keeps the chip axis; python int
    -> drops it) out of a fleet-stacked base tree."""

    def leaf(p, x):
        if _is_cw(x):
            return rram.CrossbarWeight(x.g_pos[idx], x.g_neg[idx], x.scale[idx])
        if C._is_rram_leaf(p):
            return x[idx]
        return x

    return jax.tree_util.tree_map_with_path(leaf, tree, is_leaf=_is_cw)


def _put(tree: Pytree, idx, sub: Pytree) -> Pytree:
    """Scatter the gathered-chips subtree ``sub`` back into the stacked
    tree at rows ``idx``; shared peripherals pass through untouched."""

    def leaf(p, x, s):
        if _is_cw(x):
            return rram.CrossbarWeight(
                x.g_pos.at[idx].set(s.g_pos),
                x.g_neg.at[idx].set(s.g_neg),
                x.scale.at[idx].set(s.scale),
            )
        if C._is_rram_leaf(p):
            return x.at[idx].set(s)
        return x

    return jax.tree_util.tree_map_with_path(leaf, tree, sub, is_leaf=_is_cw)


def fleet_program_model(
    base: Pytree, cfg: rram.RramConfig, chip_keys: jax.Array,
    *, mode: str = "codes",
) -> Pytree:
    """``program_model`` for a whole fleet in one stacked draw: every
    RRAM leaf is programmed under ``jax.vmap`` over the per-chip keys
    (per-leaf crc32 path fold exactly as the single-chip path), so chip
    ``i``'s codes are bitwise ``program_model(base, cfg, chip_keys[i])``.
    Digital peripherals are returned as the SAME buffers — the fleet
    shares one copy of norms/embeddings across all chips."""

    def leaf(path, x):
        if not C._is_rram_leaf(path):
            return x
        h = jnp.uint32(zlib.crc32(C._path_str(path).encode()))
        return jax.vmap(
            lambda ck: C.program_leaf(
                x, cfg, jax.random.fold_in(ck, h), mode=mode
            )
        )(chip_keys)

    return jax.tree_util.tree_map_with_path(leaf, base)


# ---------------------------------------------------------------------------
# compiled-step registry (mirrors deploy/serving.py): ONE jitted vmapped
# step per (kind, cfg, opt, trace backend) — the fleet-size axis is a
# shape handled by jax.jit's argument cache on the SAME callable, which
# is exactly what makes "calibrate 64 chips" cost one compile, not 64.
# ---------------------------------------------------------------------------

_FLEET_STEPS: Dict[Tuple, Any] = {}


def _registry_get(key: Tuple, build):
    fn = _FLEET_STEPS.get(key)
    if fn is None:
        fn = _FLEET_STEPS[key] = build()
    return fn


def fleet_compile_count(cfg) -> int:
    """Total compiled-computation count across this cfg's fleet step
    functions (any kind, any backend). Flat across chips and repeated
    same-shape calibrations — the per-chip-retrace regression counter
    (``benchmarks/fleet_bench.py`` fails if a second same-size
    calibration grows it)."""
    total = 0
    for key, fn in _FLEET_STEPS.items():
        if key[1] != cfg:
            continue
        size = getattr(fn, "_cache_size", None)
        total += size() if callable(size) else 0
    return total


def _calib_step_fn(cfg, opt: AdamW, kind: str, axes: Pytree):
    """The jitted vmapped calibration step for ``(kind, cfg, opt, active
    backend)``: chip axis on student base / adapters / optimizer / step,
    teacher base and batch broadcast."""
    n_args = 2 if kind == "cached" else 1

    def build():
        step = (make_cached_calib_step if kind == "cached"
                else make_calib_step)(cfg, opt)
        return jit_calib_step(step, wrap=lambda program: jax.vmap(
            program, in_axes=(None, axes, 0) + (None,) * n_args,
        ))

    key = (kind, cfg, opt, substrate.active_backend_name())
    return _registry_get(key, build)


def _axes_to_specs(axes_tree: Pytree) -> Pytree:
    """Chip-axis prefix tree (0/None per chip_axes) -> PartitionSpec
    prefix tree over the "data" mesh axis: chip-stacked leaves shard
    their leading dim, shared peripherals replicate."""
    return jax.tree_util.tree_map(
        lambda a: P("data") if a == 0 else P(),
        axes_tree, is_leaf=lambda v: v is None,
    )


def _fleet_state_shardings(state: CalibState, axes: Pytree, mesh) -> CalibState:
    """NamedSharding tree matching a gathered CalibState: chip-axis
    leaves distribute over "data", the teacher and shared peripherals
    replicate — the placement under which the ordinary vmapped step is
    bitwise the single-device run (chips are independent rows)."""
    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P("data"))

    def base_leaf(x, a):
        ns = dat if a == 0 else rep
        if _is_cw(x):
            return rram.CrossbarWeight(ns, ns, ns)
        return ns

    return CalibState(
        jax.tree_util.tree_map(lambda x: rep, state.teacher_base),
        jax.tree_util.tree_map(
            base_leaf, state.student_base, axes, is_leaf=_is_cw
        ),
        jax.tree_util.tree_map(lambda x: dat, state.adapters),
        jax.tree_util.tree_map(lambda x: dat, state.opt_state),
        dat,
    )


def _mesh_calib_step_fn(cfg, opt: AdamW, axes: Pytree, mesh):
    """The compressed-gradient mesh calibration step: ONE shard_map over
    the "data" axis, each device advancing its local block of chips
    against the replicated teacher-feature cache.

    The cross-device reduction is where ``optim.compress`` plugs in:
    each device scatters its local per-chip adapter gradients into a
    zero canvas at its chip offset, and ``allreduce_compressed``
    (error-feedback int8) assembles the global per-chip gradient stack —
    a mean over devices whose contributions are disjoint blocks, undone
    by the ``* n_dev`` rescale. Each device then slices its own block
    back out and applies the optimizer locally, so the update trajectory
    differs from the exact run only by the int8 quantization error,
    which the per-device residual feeds back into the next step.
    Reported losses are assembled with an EXACT psum (pre-update, so
    they are comparable step-for-step against the dense path)."""
    loss_fn = make_cached_calib_loss(cfg)
    n_dev = int(mesh.shape["data"])
    trainable = (P("data"), P("data"), P("data"))

    def build():
        def body(state, feats, batch, residual):
            dev = jax.lax.axis_index("data")
            vg = jax.vmap(
                jax.value_and_grad(loss_fn), in_axes=(0, axes, None, None)
            )
            losses, grads = vg(
                state.adapters, state.student_base, feats, batch
            )
            n_local = losses.shape[0]

            def scatter(g):
                full = jnp.zeros(
                    (n_local * n_dev,) + g.shape[1:], jnp.float32
                )
                start = (dev * n_local,) + (0,) * (g.ndim - 1)
                return jax.lax.dynamic_update_slice(
                    full, g.astype(jnp.float32), start
                )

            loss_full = jax.lax.psum(scatter(losses), "data")
            res_local = jax.tree_util.tree_map(lambda r: r[0], residual)
            g_full = jax.tree_util.tree_map(scatter, grads)
            reduced, new_res = allreduce_compressed(
                g_full, res_local, "data"
            )

            def localize(g):
                start = (dev * n_local,) + (0,) * (g.ndim - 1)
                return jax.lax.dynamic_slice(
                    g * n_dev, start, (n_local,) + g.shape[1:]
                )

            g_local = jax.tree_util.tree_map(localize, reduced)
            new_adapters, new_opt = jax.vmap(
                lambda g, o, a_: adamw_update(g, o, a_, opt)
            )(g_local, state.opt_state, state.adapters)
            new_state = CalibState(
                state.teacher_base, state.student_base, new_adapters,
                new_opt, state.step + 1,
            )
            new_residual = jax.tree_util.tree_map(
                lambda r: r[None], new_res
            )
            return new_state, {"loss": loss_full}, new_residual

        return jit_calib_step(body, wrap=lambda program: jax.shard_map(
            program, mesh=mesh,
            in_specs=(P(), _axes_to_specs(axes), trainable, P(), P(),
                      P("data")),
            out_specs=(trainable, P(), P("data")),
            check_vma=False,
        ))

    key = ("mesh_compressed", cfg, opt, substrate.active_backend_name(), mesh)
    return _registry_get(key, build)


def _logits_fn(cfg, axes: Pytree, use_adapters: bool):
    """Jitted vmapped student forward -> per-chip f32 logits."""

    def build():
        def one(base, adapters, batch):
            return T.forward(
                {"base": base, "adapters": adapters}, batch, cfg,
                use_adapters=use_adapters,
            ).astype(jnp.float32)

        return jax.jit(jax.vmap(
            one, in_axes=(axes, 0 if use_adapters else None, None)
        ))

    key = ("logits", cfg, use_adapters, substrate.active_backend_name())
    return _registry_get(key, build)


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetCalibrationReport:
    """Outcome of one batched ``Fleet.calibrate`` call."""

    chips: List[int]             # which chips this pass trained
    losses: np.ndarray           # (steps, len(chips)) per-step feature MSE
    epochs_run: int
    sram_bytes: int              # TOTAL fleet side-car bytes (all chips)
    sram_bytes_per_chip: int
    rram_bytes: int              # total resident code bytes across the fleet
    base_params: int             # per-chip logical base params
    adapter_params: int          # per-chip adapter params
    calibrated_fraction: float
    backend: str
    # registry warm-start accounting: which of ``chips`` were seeded
    # from a stable reference (parallel ``warm_sources`` names them)
    warm_started_chips: List[int] = dataclasses.field(default_factory=list)
    warm_sources: List[str] = dataclasses.field(default_factory=list)

    @property
    def initial_loss(self) -> np.ndarray:
        return self.losses[0]

    @property
    def final_loss(self) -> np.ndarray:
        return self.losses[-1]

    def summary(self) -> str:
        return (
            f"calibrated {len(self.chips)} chips x {self.epochs_run} epochs: "
            f"feature MSE {float(self.initial_loss.mean()):.6f} -> "
            f"{float(self.final_loss.mean()):.6f} (fleet mean) | "
            f"sram_bytes/chip={self.sram_bytes_per_chip} "
            f"({self.calibrated_fraction:.2%} of params) "
            f"backend={self.backend}"
        )


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------


class Fleet:
    """N deployments of one model as batched pytrees. See module docstring.

    ``self.codes`` is the stacked ground truth (chip axis on every
    ``CrossbarWeight``); ``self.base`` is what batched forwards consume
    (the codes themselves, or the stacked float read-back under
    ``dequant``). Peripheral leaves are shared single buffers."""

    def __init__(
        self, cfg, backend: str, teacher_base: Pytree, codes: Pytree,
        adapters: Pytree, teacher_key: jax.Array, program_key: jax.Array,
        n_chips: int,
    ):
        if backend not in serving.BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; available: {serving.BACKENDS}"
            )
        if n_chips < 1:
            raise ValueError(f"n_chips must be >= 1, got {n_chips}")
        self.cfg = cfg
        self.backend = backend
        self.teacher_base = teacher_base
        self.codes = codes
        self.adapters = adapters
        self.teacher_key = teacher_key
        self.program_key = program_key
        self.n_chips = int(n_chips)
        self.opt_state: Optional[Pytree] = None
        self.steps: List[int] = [0] * self.n_chips
        self.drift_hours: List[List[float]] = [[] for _ in range(self.n_chips)]
        # fault lifecycle: (spec, chips) events, and the composed
        # full-chip-axis map re-derived from them
        self.fault_events: List[Tuple[Any, Tuple[int, ...]]] = []
        self._fault_map = None
        self._refresh_base()
        self._proxy_ref = self._gamma_norms()

    # -- programming event ---------------------------------------------------

    @classmethod
    def program(
        cls, cfg, key=0, n_chips: int = 1, *, backend: str = "dequant",
    ) -> "Fleet":
        """One stacked programming event for ``n_chips`` devices sharing
        the teacher's target weights: chip ``i`` programs under
        ``fold_in(program_key, i)``, so ``Deployment.program(cfg,
        (teacher_key, fleet.chip_key(i)))`` reproduces chip ``i``
        bitwise. Adapters start identical across chips (the teacher
        init) and diverge only through per-chip calibration."""
        teacher_key, program_key = _key_pair(key)
        params = T.init_params(teacher_key, cfg)
        keys = chip_keys(program_key, n_chips)
        codes = fleet_program_model(params["base"], cfg.rram, keys)
        adapters = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * n_chips), params["adapters"]
        )
        return cls(
            cfg=cfg, backend=backend, teacher_base=params["base"],
            codes=codes, adapters=adapters, teacher_key=teacher_key,
            program_key=program_key, n_chips=n_chips,
        )

    def chip_key(self, i: int) -> jax.Array:
        """Chip ``i``'s programming key (``fold_in(program_key, i)``) —
        hand it to ``Deployment.program(cfg, (teacher_key, chip_key))``
        to rebuild that one chip independently."""
        return jax.random.fold_in(self.program_key, int(i))

    def _refresh_base(self):
        # stacked analogue of Deployment._refresh_base: pristine stacked
        # codes stay the drift clock's ground truth, consumers (batched
        # forwards, calibration, the drift/hard-fault proxies) read the
        # faulty view
        self.codes_view = substrate.faulted_codes(
            self.codes, self._fault_map, self.cfg.rram
        )
        if self.backend == "dequant":
            self.base = _dequant_like(self.codes_view, self.teacher_base)
        else:
            self.base = self.codes_view
        self._base_axes = chip_axes(self.base)
        self._codes_axes = chip_axes(self.codes)

    # -- heterogeneous drift clocks ------------------------------------------

    def field_hours(self, chip: int) -> float:
        """Chip ``chip``'s total elapsed field time."""
        return float(sum(self.drift_hours[chip]))

    def _chip_list(self, chips) -> List[int]:
        if chips is None:
            return list(range(self.n_chips))
        out = [int(c) for c in chips]
        if len(set(out)) != len(out):
            raise ValueError(f"duplicate chips in {out}")
        for c in out:
            if not 0 <= c < self.n_chips:
                raise ValueError(f"chip {c} out of range [0, {self.n_chips})")
        return out

    def advance(
        self, hours: Union[float, Sequence[float]], chips=None,
    ) -> "Fleet":
        """Let field time pass on a subset of chips (default: all).
        ``hours`` is a scalar (same tick everywhere) or a per-chip
        sequence matching ``chips``. Every affected chip draws its tick
        from ``(chip_key, event_index, variance increment over ITS own
        clock)`` — one batched dispatch, yet chip ``i``'s new codes are
        bitwise what ``Deployment.advance`` would produce, and advancing
        disjoint chips in any interleaving of calls commutes.

        ``hours=0`` entries are true no-ops (no event recorded);
        negative hours raise ``ValueError``."""
        chips = self._chip_list(chips)
        if isinstance(hours, (int, float)):
            hlist = [float(hours)] * len(chips)
        else:
            hlist = [float(h) for h in hours]
            if len(hlist) != len(chips):
                raise ValueError(
                    f"hours has {len(hlist)} entries for {len(chips)} chips"
                )
        for h in hlist:
            if h < 0:
                raise ValueError(
                    f"drift clock cannot run backwards (hours={h})"
                )
        active = [(c, h) for c, h in zip(chips, hlist) if h > 0]
        if not active:
            return self
        sigmas = [
            rram.drift_sigma_increment(self.cfg.rram, self.field_hours(c), h)
            for c, h in active
        ]
        events = [len(self.drift_hours[c]) for c, _ in active]
        # chips whose tick draws nothing (sigma == 0, e.g. relative_drift
        # 0) still age — the event is recorded but no noise is drawn,
        # exactly the single-chip early-out.
        live = [k for k, s in enumerate(sigmas) if s > 0.0]
        if live:
            idx = jnp.asarray([active[k][0] for k in live], jnp.int32)
            keys = chip_keys(self.program_key, None, idx=idx)
            sig = jnp.asarray([sigmas[k] for k in live], jnp.float32)
            ev = jnp.asarray([events[k] for k in live], jnp.uint32)
            sub = _take(self.codes, idx)
            drift = jax.vmap(
                lambda c, k, s, e: C.drift_model(
                    c, self.cfg.rram, k, sigma=s, event_index=e
                ),
                in_axes=(self._codes_axes, 0, 0, 0),
                out_axes=self._codes_axes,
            )
            new = drift(sub, keys, sig, ev)
            self.codes = _put(self.codes, idx, new)
            if self._fault_map is not None:
                # faulted fleet: re-derive the faulty view (stuck cells
                # must stay pinned over the freshly drifted codes)
                self._refresh_base()
            elif self.backend == "dequant":
                # refresh the read-back for the AFFECTED rows only — a
                # single-chip tick must not re-dequantize the whole fleet
                self.base = _put(
                    self.base, idx, _dequant_like(new, self.teacher_base)
                )
                self.codes_view = self.codes
            else:
                self.base = self.codes_view = self.codes
        for c, h in active:
            self.drift_hours[c].append(h)
        return self

    # -- fault injection -----------------------------------------------------

    def inject(self, faults, chips=None) -> "Fleet":
        """Inject device faults (a ``FaultSpec`` or a sequence) into
        ``chips`` (default: all) — a lifecycle event like drift,
        recorded for snapshot/restore replay. Each selected chip draws
        from ``fold_in(spec_key, chip)`` and non-selected chips get
        exact-identity map rows, so chip ``i``'s faulty view is bitwise
        what ``Deployment.inject(spec.for_chip(i))`` produces on the
        solo chip. Pristine stacked codes are untouched; the composed
        map re-applies at read-back, so stuck cells stay pinned through
        ``advance`` and repeat injection is a no-op."""
        specs = list(faults) if isinstance(faults, (list, tuple)) else [faults]
        chip_list = tuple(self._chip_list(chips))
        for spec in specs:
            self.fault_events.append((spec, chip_list))
        self._rebuild_fault_map()
        self._refresh_base()
        return self

    def _rebuild_fault_map(self):
        from repro.faults import build_fleet_map, compose_maps

        if not self.fault_events:
            self._fault_map = None
            return
        per_chip = _take(self.codes, 0)  # per-chip leaf shapes template
        self._fault_map = compose_maps(
            build_fleet_map(per_chip, spec, self.cfg.rram, chips, self.n_chips)
            for spec, chips in self.fault_events
        )

    # -- batched calibration -------------------------------------------------

    def calibrate(
        self, batch_or_samples: Union[Dict, int] = 10, *,
        steps: int = 20, lr: float = 1e-3, opt: Optional[AdamW] = None,
        seq_len: int = 32, chips=None, cached_teacher: Optional[bool] = None,
        loss_threshold: float = 0.0, registry=None,
        warm_start: bool = False, record: bool = True,
        mesh: Optional[Mesh] = None, grad_compress: bool = False,
    ) -> FleetCalibrationReport:
        """Algorithm 1 for ``chips`` (default: all) as ONE vmapped loop:
        the frozen teacher's features are computed once and shared by
        every chip (the per-chip teacher forward is amortized away), and
        each jitted step advances all selected chips' adapters together.
        Chip ``i``'s losses/adapters/optimizer are bitwise what an
        independent ``Deployment.calibrate`` with the same key and
        default arguments would produce.

        ``loss_threshold`` stops the shared loop early once EVERY
        selected chip's per-step loss is at or below it (the loop is one
        vmapped dispatch, so epochs are spent fleet-wide).

        Registry threading (``repro.registry``): ``warm_start=True``
        seeds all selected chips from their per-chip nearest stable
        references in one batched scatter before the loop
        (``registry/warmstart.seed_fleet``); ``record=True`` persists
        each chip's result as a versioned artifact under its own
        ``(cfg, backend, drift signature)`` key afterwards.

        Mesh parallelism: with ``mesh`` (axes ("data", ...)) the chip
        axis shards over the "data" axis — the one vmapped loop runs
        chip blocks on separate devices while the teacher-feature cache
        is broadcast once. Bitwise equal to the single-device run (chips
        are independent batch rows). ``grad_compress=True`` additionally
        routes the per-chip adapter gradients through the error-feedback
        int8 ``optim.compress.allreduce_compressed`` cross-device
        reduction (``_mesh_calib_step_fn``): losses stay exact, the
        adapter trajectory tracks the exact one within quantization
        tolerance. Requires the cached-teacher path and ``len(chips)``
        divisible by the data-axis size."""
        cfg = self.cfg
        opt = opt if opt is not None else AdamW(lr=lr)
        chips = self._chip_list(chips)
        idx = jnp.asarray(chips, jnp.int32)
        batch = calibration_batch(cfg, batch_or_samples, seq_len)
        use_cached = True if cached_teacher is None else bool(cached_teacher)
        if grad_compress and mesh is None:
            raise ValueError("grad_compress needs a mesh to reduce across")
        if mesh is not None:
            if not use_cached:
                raise ValueError(
                    "mesh fleet calibration runs the cached-teacher path; "
                    "pass cached_teacher=True (or leave it unset)"
                )
            n_dev = int(mesh.shape["data"])
            if len(chips) % n_dev:
                raise ValueError(
                    f"{len(chips)} selected chips do not divide over the "
                    f"data axis ({n_dev} devices); pad the chip selection"
                )
        if self.opt_state is None:
            self.opt_state = jax.vmap(adamw_init)(self.adapters)
        warm_recs = [None] * len(chips)
        if registry is not None and warm_start:
            from repro.registry.warmstart import seed_fleet

            warm_recs = seed_fleet(self, registry, chips)
        state = CalibState(
            self.teacher_base,
            _take(self.base, idx),
            jax.tree_util.tree_map(lambda x: x[idx], self.adapters),
            jax.tree_util.tree_map(lambda x: x[idx], self.opt_state),
            jnp.asarray([self.steps[c] for c in chips], jnp.int32),
        )
        backend_ctx = (
            substrate.use_backend("dequant")
            if self.backend != "dequant" else contextlib.nullcontext()
        )
        losses: List[np.ndarray] = []
        with backend_ctx:
            axes = self._base_axes
            if use_cached:
                feats = teacher_features(self.teacher_base, batch, cfg)
                if mesh is not None:
                    # chip-axis leaves distribute over "data"; the
                    # teacher features / batch broadcast ONCE (device_put
                    # here, not per step inside the loop)
                    rep = NamedSharding(mesh, P())
                    state = jax.device_put(
                        state, _fleet_state_shardings(state, axes, mesh)
                    )
                    feats = jax.device_put(feats, rep)
                    batch = jax.device_put(
                        batch, jax.tree_util.tree_map(lambda x: rep, batch)
                    )
                if grad_compress:
                    step_fn = _mesh_calib_step_fn(cfg, opt, axes, mesh)
                    res = {
                        "r": jax.device_put(
                            jax.tree_util.tree_map(
                                lambda x: jnp.zeros(
                                    (int(mesh.shape["data"]),) + x.shape,
                                    jnp.float32,
                                ),
                                state.adapters,
                            ),
                            NamedSharding(mesh, P("data")),
                        )
                    }

                    def run(s):
                        s2, metrics, res["r"] = step_fn(
                            s, feats, batch, res["r"]
                        )
                        return s2, metrics
                else:
                    step_fn = _calib_step_fn(cfg, opt, "cached", axes)
                    run = lambda s: step_fn(s, feats, batch)
            else:
                step_fn = _calib_step_fn(cfg, opt, "full", axes)
                run = lambda s: step_fn(s, batch)
            for _ in range(steps):
                state, metrics = run(state)
                losses.append(np.asarray(metrics["loss"], np.float32))
                if loss_threshold and bool(
                    np.all(losses[-1] <= loss_threshold)
                ):
                    break
        if mesh is not None:
            # pull the sharded result back before the host-side scatter
            state = jax.device_get(state)
        self.adapters = jax.tree_util.tree_map(
            lambda full, sub: full.at[idx].set(sub),
            self.adapters, state.adapters,
        )
        self.opt_state = jax.tree_util.tree_map(
            lambda full, sub: full.at[idx].set(sub),
            self.opt_state, state.opt_state,
        )
        new_steps = np.asarray(state.step)
        for j, c in enumerate(chips):
            self.steps[c] = int(new_steps[j])
        # recalibration resets the drift baseline for the chips it touched
        cur = self._gamma_norms()
        self._proxy_ref = [
            ref.at[idx].set(now[idx]) for ref, now in zip(self._proxy_ref, cur)
        ]
        n_base, n_adapters = T.count_params(
            {"base": self.teacher_base,
             "adapters": jax.tree_util.tree_map(lambda x: x[0], self.adapters)}
        )
        total_sram = sram_bytes(self.adapters)
        report = FleetCalibrationReport(
            chips=chips,
            losses=np.stack(losses),
            epochs_run=len(losses),
            sram_bytes=total_sram,
            sram_bytes_per_chip=total_sram // self.n_chips,
            rram_bytes=rram_bytes(self.codes),
            base_params=n_base,
            adapter_params=n_adapters,
            calibrated_fraction=n_adapters / max(n_base, 1),
            backend=self.backend,
            warm_started_chips=[
                c for c, r in zip(chips, warm_recs) if r is not None
            ],
            warm_sources=[
                r.name for r in warm_recs if r is not None
            ],
        )
        if registry is not None and record:
            self._record_artifacts(registry, report, warm_recs)
        return report

    def _record_artifacts(self, registry, report, warm_recs) -> None:
        """Persist each calibrated chip's run as its own versioned
        artifact (its drift signature differs per chip, so each files
        under — and is stability-checked against — its own key)."""
        from repro.deploy.deployment import CalibrationReport

        for j, c in enumerate(report.chips):
            rec = warm_recs[j] if j < len(warm_recs) else None
            chip_report = CalibrationReport(
                losses=[float(x) for x in report.losses[:, j]],
                epochs_run=report.epochs_run,
                sram_bytes=report.sram_bytes_per_chip,
                rram_bytes=report.rram_bytes // self.n_chips,
                base_params=report.base_params,
                adapter_params=report.adapter_params,
                calibrated_fraction=report.calibrated_fraction,
                backend=report.backend,
                drift_events=len(self.drift_hours[c]),
                warm_started=rec is not None,
                warm_source=None if rec is None else rec.name,
            )
            registry.record(
                self.cfg, self.backend, self.chip_signature(c),
                adapters=jax.tree_util.tree_map(
                    lambda x: x[c], self.adapters
                ),
                opt_state=jax.tree_util.tree_map(
                    lambda x: x[c], self.opt_state
                ),
                report=chip_report,
                extra_meta={"chip": int(c)},
            )

    def chip_signature(self, i: int) -> np.ndarray:
        """Chip ``i``'s registry signature (device feature from its
        per-chip programming key + its own drift/fault state) — what its
        calibration artifacts file under and warm-start lookups rank
        against."""
        from repro.registry.warmstart import drift_signature

        i = int(i)
        return drift_signature(
            self.cfg.rram, self.chip_key(i),
            field_hours=self.field_hours(i),
            drift_events=len(self.drift_hours[i]),
            fault_events=sum(
                1 for _, chips in self.fault_events if i in chips
            ),
        )

    def reset_adapters(self) -> "Fleet":
        """Discard every chip's SRAM side-cars back to the fresh
        (output-preserving) teacher init and clear the optimizer — the
        "calibrate from scratch" state a new process starts from. The
        stacked codes and per-chip drift clocks are untouched."""
        fresh = T.init_params(self.teacher_key, self.cfg)["adapters"]
        self.adapters = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * self.n_chips), fresh
        )
        self.opt_state = None
        self.steps = [0] * self.n_chips
        return self

    # -- drift proxy ---------------------------------------------------------

    def _gamma_norms(self) -> List[jax.Array]:
        out: List[jax.Array] = []

        def leaf(x):
            if _is_cw(x):
                out.append(substrate.code_column_norms(x))
            return x

        # norms read the FAULTY view: the proxies must see what the
        # forwards (and the merged DoRA γ) actually read back
        jax.tree_util.tree_map(leaf, self.codes_view, is_leaf=_is_cw)
        return out

    def drift_proxy(self) -> np.ndarray:
        """(n_chips,) forward-free drift signal: mean relative movement
        of per-layer code column norms since each chip's LAST
        calibration (or programming). Conductance relaxation perturbs
        exactly the norms the merged DoRA γ divides by, so this tracks
        how stale each chip's SRAM compensation has become — at the cost
        of a read-back reduction, no activations, no matmuls. The
        ``RecalibrationScheduler`` recalibrates a chip only when this
        crosses its threshold."""
        vals = []
        for now, ref in zip(self._gamma_norms(), self._proxy_ref):
            rel = jnp.abs(now - ref) / jnp.maximum(jnp.abs(ref), 1e-8)
            vals.append(jnp.mean(rel.reshape(self.n_chips, -1), axis=1))
        return np.asarray(jnp.mean(jnp.stack(vals), axis=0))

    def hard_fault_proxy(self) -> np.ndarray:
        """(n_chips,) hard-fault signal: MAX relative movement of any
        single code column norm since the chip's last calibration.

        Drift is a diffusion — per-column norm movement is small and
        DISTRIBUTED, so even the worst column moves only a few standard
        errors above the mean the drift proxy reads. Stuck/saturated/
        retention-hit cells instead slam individual columns (a cell
        pinned to LRS jumps that one column's norm by tens of percent),
        a localized jump drift alone cannot produce. The scheduler
        thresholds this separately to tell "drifted — recalibrate"
        from "hard-faulted — recalibrate harder and flag the chip"."""
        vals = []
        for now, ref in zip(self._gamma_norms(), self._proxy_ref):
            rel = jnp.abs(now - ref) / jnp.maximum(jnp.abs(ref), 1e-8)
            vals.append(jnp.max(rel.reshape(self.n_chips, -1), axis=1))
        return np.asarray(jnp.max(jnp.stack(vals), axis=0))

    def logit_mse(self, batch: Dict, *, use_adapters: bool = True) -> np.ndarray:
        """(n_chips,) teacher/student logit MSE — the fleet-wide
        degradation/recovery metric. One teacher forward, one vmapped
        student forward (codes-resident fleets read back through the
        differentiable ``dequant`` trace, like calibration)."""
        t = T.forward(
            {"base": self.teacher_base, "adapters": {}}, batch, self.cfg,
            use_adapters=False,
        ).astype(jnp.float32)
        backend_ctx = (
            substrate.use_backend("dequant")
            if self.backend != "dequant" else contextlib.nullcontext()
        )
        with backend_ctx:
            fn = _logits_fn(self.cfg, self._base_axes, use_adapters)
            s = fn(self.base, self.adapters if use_adapters else {}, batch)
        return np.asarray(jnp.mean((s - t[None]) ** 2, axis=tuple(range(1, s.ndim))))

    # -- per-chip extraction / serving ---------------------------------------

    def chip(self, i: int) -> Deployment:
        """Slice chip ``i`` back out as a plain ``Deployment`` (bitwise:
        the same codes/adapters/optimizer/history, chip axis dropped).
        The fleet and the extracted deployment do not alias mutable
        state — advancing one does not move the other."""
        i = int(i)
        if not 0 <= i < self.n_chips:
            raise ValueError(f"chip {i} out of range [0, {self.n_chips})")
        dep = Deployment(
            cfg=self.cfg, backend=self.backend,
            teacher_base=self.teacher_base,
            codes=_take(self.codes, i),
            adapters=jax.tree_util.tree_map(lambda x: x[i], self.adapters),
            teacher_key=self.teacher_key, program_key=self.chip_key(i),
        )
        dep.drift_hours = list(self.drift_hours[i])
        dep.step = int(self.steps[i])
        if self.opt_state is not None:
            dep.opt_state = jax.tree_util.tree_map(
                lambda x: x[i], self.opt_state
            )
        # replay this chip's fault events with the chip index folded in —
        # bitwise the fleet map's row i by the shared per-chip keying
        specs = [
            spec.for_chip(i) for spec, chips in self.fault_events
            if i in chips
        ]
        if specs:
            dep.inject(specs)
        return dep

    def serve(self, chip: int) -> serving.ServeSession:
        """Serve chip ``chip``. Sessions share the per-``(cfg,
        backend)`` compiled-step registry, so serving the whole fleet
        chip-by-chip compiles the decode stack once, not N times."""
        return self.chip(chip).serve()

    # -- accounting ----------------------------------------------------------

    def sram_bytes(self) -> int:
        """Total SRAM side-car bytes across the fleet (N x per-chip)."""
        return sram_bytes(self.adapters)

    def rram_bytes(self) -> int:
        """Total resident code bytes across the fleet."""
        return rram_bytes(self.codes)

    # -- persistence ---------------------------------------------------------

    def snapshot(self, directory_or_manager, *, blocking: bool = True) -> int:
        """Checkpoint the fleet's mutable state: stacked adapters +
        optimizer, per-chip lifecycle records (keys, heterogeneous drift
        histories, step counters) and the drift-proxy baselines. The
        stacked base is NOT stored — restore replays programming and
        every per-chip drift tick."""
        manager = as_manager(directory_or_manager)
        if self.opt_state is None:
            self.opt_state = jax.vmap(adamw_init)(self.adapters)
        # a key that grows with ANY state change (calibration steps OR
        # drift events on any chip) — max(steps) alone stays flat across
        # drift-only maintenance ticks and would silently overwrite the
        # previous snapshot directory
        counts = [len(h) for h in self.drift_hours]
        step = int(sum(self.steps) + sum(counts))
        width = max(counts) if counts else 0
        padded = np.zeros((self.n_chips, width), np.float64)
        for c, hs in enumerate(self.drift_hours):
            padded[c, : len(hs)] = hs
        lifecycle = {
            "teacher_key": np.asarray(self.teacher_key),
            "program_key": np.asarray(self.program_key),
            "steps": np.asarray(self.steps, np.int64),
            "drift_hours": padded,
            "drift_counts": np.asarray(counts, np.int64),
        }
        manager.save(
            step,
            {"adapters": self.adapters, "opt": self.opt_state,
             "lifecycle": lifecycle, "proxy_ref": list(self._proxy_ref)},
            blocking=blocking,
        )
        meta = {
            "format": 1, "backend": self.backend,
            "arch": getattr(self.cfg, "name", None),
            "n_chips": self.n_chips,
            "fault_events": [
                [spec.to_dict(), list(chips)]
                for spec, chips in self.fault_events
            ],
        }
        with open(os.path.join(manager.directory, _FLEET_META), "w") as f:
            json.dump(meta, f)
        return step

    @classmethod
    def restore(
        cls, cfg, directory, *, step: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> "Fleet":
        """Rebuild a fleet from a snapshot: re-program all chips from
        the recorded keys, replay every chip's drift history in its own
        order (heterogeneous histories replay round-robin — chip
        independence makes cross-chip order irrelevant), then load the
        stacked adapters/optimizer and proxy baselines. Bitwise equal to
        the snapshotted fleet."""
        manager = as_manager(directory)
        if step is None:
            step = manager.latest_step()
        if step is None:
            raise FileNotFoundError(f"no snapshots in {directory}")
        meta_path = os.path.join(manager.directory, _FLEET_META)
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
                "steps": np.zeros((0,), np.int64),
                "drift_hours": np.zeros((0, 0), np.float64),
                "drift_counts": np.zeros((0,), np.int64),
            }},
        )["lifecycle"]
        n_chips = int(meta.get("n_chips", len(life["steps"])))
        fleet = cls.program(
            cfg, (life["teacher_key"], life["program_key"]),
            n_chips=n_chips, backend=backend,
        )
        counts = np.asarray(life["drift_counts"], np.int64)
        padded = np.asarray(life["drift_hours"], np.float64)
        for r in range(int(counts.max()) if counts.size else 0):
            chips = [c for c in range(n_chips) if counts[c] > r]
            fleet.advance([float(padded[c, r]) for c in chips], chips=chips)
        for event in meta.get("fault_events") or []:
            # commutes with drift replay: faults never touch the
            # pristine codes, the view re-derives after every event
            from repro.faults import FaultSpec

            spec_dict, chips = event
            fleet.inject(FaultSpec.from_dict(spec_dict), chips=chips)
        restored = manager.restore(
            step,
            {"adapters": fleet.adapters,
             "opt": jax.vmap(adamw_init)(fleet.adapters),
             "proxy_ref": fleet._gamma_norms()},
        )
        fleet.adapters = restored["adapters"]
        fleet.opt_state = restored["opt"]
        fleet._proxy_ref = [jnp.asarray(x) for x in restored["proxy_ref"]]
        fleet.steps = [int(s) for s in life["steps"]]
        return fleet


def chip_keys(
    program_key: jax.Array, n_chips: Optional[int], *, idx=None
) -> jax.Array:
    """Stacked per-chip programming keys ``fold_in(program_key, i)`` for
    ``i in range(n_chips)`` (or the explicit ``idx`` array)."""
    if idx is None:
        idx = jnp.arange(n_chips, dtype=jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(program_key, i))(
        jnp.asarray(idx, jnp.uint32)
    )
