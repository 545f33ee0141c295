"""Pluggable execution backends over resident crossbar codes.

A deployment programs the model once (``calibrate.program_model``); from
then on the RRAM array is a frozen uint8 ``(G+, G-, scale)`` triple that
is *never rewritten*. What varies is how a forward pass reads it:

  * ``codes``     — the deployment path. Codes stay resident (uint8 in
                    HBM); the fused ``dora_linear`` Pallas kernel
                    dequantizes in-register per tile and applies the
                    DoRA epilogue. Interpret mode on CPU hosts.
  * ``dequant``   — read the array back to floats per call and run the
                    plain jnp path. Differentiable w.r.t. the adapters,
                    so calibration/training over a codes-resident
                    student uses this backend.
  * ``codes_adc`` — ADC-faithful ``crossbar_mvm`` kernel (saturating
                    ADC per 256-row tile) plus digital low-rank/DoRA
                    compensation. Fidelity studies.

The backend is selected per-deployment with the ``use_backend`` context
manager (read at trace time, so wrap the ``jax.jit`` trace in it) or
per-call via ``crossbar_linear(..., backend=...)``. Float weights never
reach this module — ``models/layers.py::linear`` dispatches here only
for ``CrossbarWeight`` leaves.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import dora as dora_lib
from repro.core.dora import AdapterConfig
from repro.core.rram import CrossbarWeight, RramConfig, dequantize
from repro.substrate import exec as X
from repro.substrate.prepared import (
    PreparedCrossbar,
    ShardedPrepared,
    prepared_ref_forward,
    rimc_linear_prepared,
    tp_column_allgather,
)

DEFAULT_BACKEND = "codes"

_REGISTRY: Dict[str, "Backend"] = {}
_ACTIVE = threading.local()


class Backend:
    """One way to execute Y = f(X, resident codes, adapter)."""

    name: str = "abstract"

    def linear(
        self,
        x: jax.Array,
        xw: CrossbarWeight,
        adapter: Optional[dict],
        acfg: AdapterConfig,
    ) -> jax.Array:
        raise NotImplementedError


def register_backend(cls):
    """Class decorator: instantiate and register under ``cls.name``."""
    _REGISTRY[cls.name] = cls()
    return cls


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown substrate backend {name!r}; "
            f"available: {available_backends()}"
        ) from None


def available_backends():
    return tuple(sorted(_REGISTRY))


@contextlib.contextmanager
def use_backend(name: str, **options):
    """Bind the ambient backend (plus backend-specific keyword
    ``options``, e.g. ``code_max``/``adc_bits`` for ``codes_adc``) for
    CrossbarWeight leaves.

    Backend choice is a Python-level (static) decision: it must be
    active while jit TRACES the function, not when the compiled
    function runs. CAUTION: the backend is NOT part of the jit cache
    key — calling one already-jitted function under two different
    ``use_backend`` scopes hits the first trace's cache and silently
    reuses its backend. Jit inside the scope (what launch/serve.py
    does by rebuilding its step lambdas per call), or thread the
    explicit ``backend=`` argument through ``layers.linear``."""
    get_backend(name)  # validate eagerly
    prev = getattr(_ACTIVE, "val", None)
    _ACTIVE.val = (name, options)
    try:
        yield
    finally:
        _ACTIVE.val = prev


def active_backend_name() -> str:
    val = getattr(_ACTIVE, "val", None)
    return val[0] if val else DEFAULT_BACKEND


def _active_options() -> dict:
    val = getattr(_ACTIVE, "val", None)
    return val[1] if val else {}


def active_backend_key() -> tuple:
    """Hashable (name, sorted options) identity of the ambient backend —
    what trace-level caches (the serving step registry) must key on,
    since the options change traced behaviour just like the name does
    (e.g. ``accum="int8"`` vs the f32 path)."""
    val = getattr(_ACTIVE, "val", None)
    name, options = val if val else (DEFAULT_BACKEND, {})
    return (name, tuple(sorted(options.items())))


def crossbar_linear(
    x: jax.Array,
    xw: CrossbarWeight,
    adapter: Optional[dict],
    acfg: AdapterConfig,
    *,
    backend: Optional[str] = None,
) -> jax.Array:
    """Execute one RimcLinear over resident codes via the selected
    backend. This is the choke point ``models/layers.py::linear``
    dispatches to whenever a base leaf is a ``CrossbarWeight``.

    An explicit ``backend=`` ignores the ambient scope (and its
    options); the ambient scope's options are forwarded to the
    backend's ``linear``."""
    if backend is not None:
        return get_backend(backend).linear(x, xw, adapter or {}, acfg)
    return get_backend(active_backend_name()).linear(
        x, xw, adapter or {}, acfg, **_active_options()
    )


# ---------------------------------------------------------------------------
# helpers shared by backends
# ---------------------------------------------------------------------------


def _gamma_for(xw: CrossbarWeight, adapter: dict, acfg) -> Optional[jax.Array]:
    """(1, N) DoRA epilogue scale for the fused kernel, or None for
    LoRA/no-adapter (identity epilogue)."""
    if not adapter or acfg.kind != "dora":
        return None
    if "dora_m_merged" in adapter:
        # Algorithm 2 line 12 already folded M/||W_r + A@B|| at deployment.
        return adapter["dora_m_merged"].astype(jnp.float32)[None, :]
    # unmerged (calibration-time) DoRA: the norm is a digital precompute —
    # it reads the codes back once, outside the MVM hot path.
    return X.dora_gamma(xw, adapter)


def _zero_adapter(k: int, n: int) -> dict:
    """Rank-1 all-zero side-car: lets the fused kernel serve layers that
    have no adapter (pure-RRAM teacher path) without a second kernel."""
    return {
        "lora_a": jnp.zeros((k, 1), jnp.float32),
        "lora_b": jnp.zeros((1, n), jnp.float32),
    }


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


@register_backend
class DequantBackend(Backend):
    """Read codes back to floats per call; plain jnp forward. This is the
    only differentiable-path backend (gradients flow to the adapter; the
    uint8 codes are constants), so calibration over a codes-resident
    student runs under ``use_backend('dequant')``."""

    name = "dequant"

    def linear(self, x, xw, adapter, acfg):
        if isinstance(xw, ShardedPrepared):
            raise TypeError(
                "dequant reads full-extent prepared leaves; a sharded "
                "serve tree only executes inside the codes backend's "
                "shard_map decode step"
            )
        if isinstance(xw, PreparedCrossbar):
            # prepared trees bake their adapters in; the float view is
            # the true-extent reference forward
            return prepared_ref_forward(x, xw)
        w = dequantize(xw)
        return dora_lib.adapted_forward(x, w, adapter, acfg)


@register_backend
class CodesBackend(Backend):
    """Deployment path: fused Pallas kernel over resident uint8 codes.
    HBM holds 2 bytes/weight of codes (never a float W_r); the dequant
    happens in-register per (bk, bn) tile and the DoRA low-rank +
    magnitude ride the same K loop (kernels/dora_linear.py)."""

    name = "codes"

    def linear(self, x, xw, adapter, acfg, *, accum="f32"):
        if isinstance(xw, ShardedPrepared):
            # tensor-parallel leaf inside a shard_map decode step: run
            # the ordinary prepared kernel on this device's column
            # slice, then the zero-scatter psum epilogue rebuilds the
            # full activation bitwise (columns are disjoint).
            y = rimc_linear_prepared(x, xw.local, accum=accum)
            return tp_column_allgather(y, xw.n_total, xw.axis)
        if isinstance(xw, PreparedCrossbar):
            # serve-time prepared leaf: operands already padded/fused
            # (+ s8-recoded for int8); per-call work is the x pad only
            return rimc_linear_prepared(x, xw, accum=accum)
        gamma = _gamma_for(xw, adapter, acfg)
        if not adapter or acfg.kind == "none":
            adapter = _zero_adapter(xw.g_pos.shape[-2], xw.g_pos.shape[-1])
        if gamma is None:
            gamma = jnp.ones((1, xw.g_pos.shape[-1]), jnp.float32)
        return X.rimc_linear(x, xw, adapter, gamma, accum=accum)


_ADC_DEFAULTS = RramConfig()


def resolve_adc_limits(rram_cfg, code_max, adc_bits):
    """Single source of truth for the ADC-faithful backend's limits: the
    deployment's ``RramConfig``. An explicit ``code_max``/``adc_bits``
    that CONFLICTS with a provided config raises (it used to be silently
    accepted, letting a session serve with an ADC the array was never
    programmed for); with no config, explicit values apply and the
    defaults mirror ``RramConfig()``."""
    if rram_cfg is not None:
        for name, explicit, want in (
            ("code_max", code_max, rram_cfg.code_max),
            ("adc_bits", adc_bits, rram_cfg.adc_bits),
        ):
            if explicit is not None and int(explicit) != int(want):
                raise ValueError(
                    f"codes_adc {name}={explicit} conflicts with the "
                    f"deployment's RramConfig.{name}={want}; the RramConfig "
                    f"is the single source of truth — drop the override or "
                    f"change the config"
                )
        return int(rram_cfg.code_max), int(rram_cfg.adc_bits)
    return (
        int(_ADC_DEFAULTS.code_max if code_max is None else code_max),
        int(_ADC_DEFAULTS.adc_bits if adc_bits is None else adc_bits),
    )


@register_backend
class CodesAdcBackend(Backend):
    """ADC-faithful analog chain: saturating ADC per 256-row crossbar
    activation (kernels/crossbar_mvm.py), then the DoRA compensation is
    applied digitally — exactly the paper's periphery split.

    ``code_max``/``adc_bits`` come from the deployment's ``RramConfig``
    (pass ``rram_cfg=`` or let ``serving.backend_scope`` plumb it);
    conflicting explicit overrides raise via ``resolve_adc_limits``."""

    name = "codes_adc"

    def linear(
        self, x, xw, adapter, acfg, *,
        rram_cfg=None, code_max=None, adc_bits=None,
    ):
        code_max, adc_bits = resolve_adc_limits(rram_cfg, code_max, adc_bits)
        if isinstance(xw, PreparedCrossbar):
            raise TypeError(
                "codes_adc reads raw per-leaf codes; prepared (fused/"
                "padded) trees are codes-backend serving artifacts"
            )
        y = X.rimc_mvm_adc(x, xw, code_max=code_max, adc_bits=adc_bits)
        y = y.astype(jnp.float32)
        if adapter and "lora_a" in adapter:
            a = adapter["lora_a"].astype(jnp.float32)
            b = adapter["lora_b"].astype(jnp.float32)
            y = y + (x.astype(jnp.float32) @ a) @ b
        gamma = _gamma_for(xw, adapter, acfg)
        if gamma is not None:
            y = y * gamma
        return y.astype(x.dtype)
