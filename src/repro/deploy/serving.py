"""Serving half of the deployment lifecycle: backend scoping, the
prefill/decode step functions, and the ``ServeSession`` handle returned
by ``Deployment.serve()``.

This module owns what ``launch/serve.py`` used to wire by hand (that
module now delegates here): the RRAM base is frozen (and drifted);
accuracy comes from the DoRA side-cars that were calibrated in SRAM.
``merge_magnitude`` (Algorithm 2 line 12) folds the DoRA column norms
once at serve-session creation so each decode matmul pays only the
low-rank epilogue.

Compiled step functions are built ONCE per ``(cfg, backend)`` and reused
across every request and session (``decode_step_fn`` / ``prefill_fn``).
The old code re-wrapped ``jax.jit`` around a fresh lambda on every
``prefill_and_cache``/``generate`` call, so each request retraced and
recompiled the whole decode stack; the registry below is the fix, and
``compile_count`` exposes the counter the regression tests pin down.
The continuous-batching engine over these steps lives in
``repro/deploy/engine.py``.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import substrate

BACKENDS = ("dequant", "codes", "codes_adc")


def backend_scope(backend: str, cfg=None, **options):
    """Context manager binding the substrate backend for trace time.

    EVERY backend binds explicitly — including ``dequant``. It used to
    return a nullcontext, which left the ambient default ("codes")
    active: the step registry below then keyed dequant and codes traces
    identically and both backends shared one jitted callable, doubling
    the warm compile count on the codes path. Binding makes the
    registry key (via ``substrate.active_backend_key``) honest.

    Substrate-aware scoping: passing the model config plumbs its
    ``RramConfig`` into the ADC-faithful backend automatically — the
    config is the single source of truth for ``code_max``/``adc_bits``,
    and an explicit option that CONFLICTS with it raises ``ValueError``
    (it used to be silently accepted, letting a session serve with an
    ADC the array was never programmed for). ``ServeSession`` always
    passes its deployment's config, so sessions never serve with a
    mismatched ADC. Extra ``options`` (e.g. ``accum="int8"``) forward
    to the backend's ``linear``.
    """
    if backend == "codes_adc" and cfg is not None:
        from repro.substrate.backends import resolve_adc_limits

        code_max, adc_bits = resolve_adc_limits(
            cfg.rram, options.get("code_max"), options.get("adc_bits")
        )
        options["code_max"] = code_max
        options["adc_bits"] = adc_bits
    return substrate.use_backend(backend, **options)


# ---------------------------------------------------------------------------
# compiled-step registry (the retrace fix)
# ---------------------------------------------------------------------------
#
# The substrate backend is read at TRACE time (substrate.use_backend), so
# a jitted step is only reusable under the backend it was traced with —
# the registry key is (cfg, active backend identity, mesh). The identity
# includes the backend OPTIONS, not just the name: ``accum="int8"`` and
# f32 trace to different programs under the same name. ``mesh`` is None
# for single-device steps; mesh-native steps wrap the same transformer
# body in ``shard_map`` and are keyed per mesh so an elastic re-mesh
# builds fresh steps without evicting the old mesh's. Shape variation
# within one entry (batch size, prompt length) is handled by jax.jit's
# own argument cache on the SAME callable, which is exactly what
# rebuilding the lambda per call threw away.

_STEP_REGISTRY: Dict[Tuple, "jax.stages.Wrapped"] = {}


def _registry_get(kind: str, cfg, build, mesh: Optional[Mesh] = None):
    key = (kind, cfg, substrate.active_backend_key(), mesh)
    fn = _STEP_REGISTRY.get(key)
    if fn is None:
        fn = _STEP_REGISTRY[key] = build()
    return fn


def decode_step_fn(cfg, mesh: Optional[Mesh] = None, params=None):
    """The jitted batched decode step for ``(cfg, active backend,
    mesh)``, built once and shared by every request, session, and the
    engine. ``pos`` is a (B,) vector of per-slot clocks (scalars
    broadcast).

    With a mesh, the same transformer body runs under ``shard_map``:
    params follow ``substrate.serve_param_specs`` (column-sharded
    prepared operands over the "model" axis, the DoRA epilogue psum
    inside the backend), tokens/cache/logits replicate. ``params`` (the
    session's sharded tree) is required then — the in_specs are derived
    from which leaves are actually wrapped."""
    from repro.models import transformer as T

    def decode_step(p, c, t, i):   # the program's name: jit_decode_step
        return T.decode_step(p, c, t, i, cfg)

    if mesh is None:
        return _registry_get("decode", cfg, lambda: jax.jit(decode_step))
    if params is None:
        raise ValueError("mesh decode steps derive in_specs from params")

    def build():
        specs = substrate.serve_param_specs(params)
        sm = jax.shard_map(
            decode_step,
            mesh=mesh,
            in_specs=(specs, P(), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sm)

    return _registry_get("decode", cfg, build, mesh=mesh)


def prefill_fn(cfg, mesh: Optional[Mesh] = None, params=None):
    """The jitted fused prefill for ``(cfg, active backend, mesh)``: one
    full-sequence forward returning (last logits, decode cache) —
    ``max_len`` is static (cache buffer extent). The mesh path is
    decoder-only (no enc_embeds)."""
    from repro.models import transformer as T

    if mesh is None:
        return _registry_get(
            "prefill", cfg,
            lambda: jax.jit(
                lambda p, t, max_len, e=None, pe=None: T.prefill(
                    p, t, cfg, max_len, e, pe
                ),
                static_argnums=(2,),
            ),
        )
    if params is None:
        raise ValueError("mesh prefill steps derive in_specs from params")

    def build():
        specs = substrate.serve_param_specs(params)

        def fn(p, t, max_len, e=None, pe=None):
            if e is not None or pe is not None:
                raise ValueError(
                    "mesh serving is decoder-only (no enc_embeds/patch_embeds)"
                )
            sm = jax.shard_map(
                lambda p, t: T.prefill(p, t, cfg, max_len, None),
                mesh=mesh,
                in_specs=(specs, P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
            return sm(p, t)

        return jax.jit(fn, static_argnums=(2,))

    return _registry_get("prefill", cfg, build, mesh=mesh)


def prefill_chunk_fn(cfg, mesh: Optional[Mesh] = None, params=None):
    """The jitted chunked-prefill step for ``(cfg, active backend,
    mesh)``: advance a live decode cache by one bucketed prompt chunk at
    per-slot positions. ``max_len``/``prefix`` are static; the chunk
    bucket width varies through jit's argument cache, which the engine
    bounds to a pow-2 set."""
    from repro.models import transformer as T

    if mesh is None:
        def prefill_chunk(p, t, c, pos0, nv, max_len, prefix=0):
            return T.prefill_chunk(p, t, c, pos0, nv, cfg, max_len, prefix)

        return _registry_get(
            "prefill_chunk", cfg,
            lambda: jax.jit(prefill_chunk, static_argnums=(5, 6)),
        )
    if params is None:
        raise ValueError("mesh chunk steps derive in_specs from params")

    def build():
        specs = substrate.serve_param_specs(params)

        def prefill_chunk(p, t, c, pos0, nv, max_len, prefix=0):
            sm = jax.shard_map(
                lambda p, t, c, pos0, nv: T.prefill_chunk(
                    p, t, c, pos0, nv, cfg, max_len, prefix
                ),
                mesh=mesh,
                in_specs=(specs, P(), P(), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
            return sm(p, t, c, pos0, nv)

        return jax.jit(prefill_chunk, static_argnums=(5, 6))

    return _registry_get("prefill_chunk", cfg, build, mesh=mesh)


def prefill_vision_fn(cfg, mesh: Optional[Mesh] = None):
    """The jitted vision-prefix admission step: scatter
    ``cfg.vision_tokens`` bidirectional patch positions into a fresh slot
    cache. One static shape per config — compiles exactly once."""
    from repro.models import transformer as T

    if mesh is not None:
        raise ValueError("mesh serving has no vision-prefix path")
    return _registry_get(
        "prefill_vision", cfg,
        lambda: jax.jit(
            lambda p, pe, c, max_len: T.prefill_vision(p, pe, c, cfg, max_len),
            static_argnums=(3,),
        ),
    )


def encode_fn(cfg, mesh: Optional[Mesh] = None):
    """The jitted encoder admission step: run the (bidirectional) encoder
    once and scatter every decoder layer's cross-attention K/V lines +
    ``enc_len`` into a slot cache."""
    from repro.models import transformer as T

    if mesh is not None:
        raise ValueError("mesh serving is decoder-only (no encoder)")
    return _registry_get(
        "encode", cfg,
        lambda: jax.jit(lambda p, c, e: T.encode_into_cache(p, c, e, cfg)),
    )


STEP_KINDS = ("decode", "prefill", "prefill_chunk", "prefill_vision", "encode")


def compile_count(cfg, mesh: Optional[Mesh] = None) -> int:
    """Total compiled-computation count across this (cfg, backend,
    mesh)'s step functions. Flat across repeated same-shape requests —
    the regression tests and ``benchmarks/serve_bench.py`` track it as
    the retrace counter."""
    total = 0
    for kind in STEP_KINDS:
        fn = _STEP_REGISTRY.get(
            (kind, cfg, substrate.active_backend_key(), mesh)
        )
        if fn is not None:
            # _cache_size is private jax API; the zero-recompile test's
            # `warm > 0` assertion is the canary if an upgrade drops it
            size = getattr(fn, "_cache_size", None)
            total += size() if callable(size) else 0
    return total


def prefill_and_cache(
    params, tokens, cfg, max_len: int, enc_embeds=None,
    mesh: Optional[Mesh] = None, patch_embeds=None,
):
    """Fused prefill: ONE full-sequence forward computes every layer's
    K/V (MLA latents, recurrent states) batched over the prompt and
    scatters them into the decode cache — replaces the old per-token
    ``decode_step`` Python loop (S sequential dispatches). Returns
    ``(last_logits (B,1,V), cache)``; parity with the step-by-step loop
    is pinned in tests/test_engine.py."""
    if cfg.encoder_layers and enc_embeds is None:
        raise ValueError("encoder-decoder config needs enc_embeds")
    return prefill_fn(cfg, mesh, params)(
        params, tokens, int(max_len), enc_embeds, patch_embeds
    )


def _next_token(logits, temperature: float, key):
    """Greedy or temperature sampling of the next token; returns
    (token, advanced key). EVERY position — including the first generated
    token — goes through this, so ``temperature > 0`` is honored from
    token 0 (the old serve loop argmax'd the first token regardless)."""
    if temperature > 0 and key is not None:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, logits[:, -1] / temperature)[:, None]
        return tok.astype(jnp.int32), key
    return jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32), key


def _check_sampling_args(temperature: float, key) -> None:
    """Surface intent mismatches instead of silently ignoring one of the
    two sampling knobs."""
    if temperature > 0 and key is None:
        raise ValueError("temperature > 0 needs a PRNG key")
    if temperature == 0 and key is not None:
        raise ValueError(
            "a PRNG key was passed but temperature == 0 samples greedily "
            "and would ignore it; pass temperature > 0 or drop the key"
        )


def generate(
    params, prompt: jax.Array, cfg, *, gen_len: int = 16,
    temperature: float = 0.0, enc_embeds=None, patch_embeds=None, key=None,
) -> Tuple[np.ndarray, float]:
    """Reference single-stream generation loop: fused prefill, then
    ``gen_len - 1`` decode steps (the first token comes from the prefill
    logits). Returns ``(tokens (B, gen_len), dt)`` where ``dt`` covers
    exactly those decode steps — so decode tok/s is
    ``B * (gen_len - 1) / dt``, with no prefill-sampled token smuggled
    into a decode-only timer. ``patch_embeds`` prepends a prefix-LM
    vision prefix; the decode clock then starts at ``P + S``. The
    continuous-batching path is ``repro.deploy.engine.ServeEngine``."""
    _check_sampling_args(temperature, key)
    if gen_len < 1:
        raise ValueError(f"gen_len must be >= 1, got {gen_len}")
    b, s = prompt.shape
    prefix = 0 if patch_embeds is None else patch_embeds.shape[1]
    max_len = prefix + s + gen_len
    logits, cache = prefill_and_cache(
        params, prompt, cfg, max_len, enc_embeds, patch_embeds=patch_embeds
    )
    step = decode_step_fn(cfg)
    tok, key = _next_token(logits, temperature, key)
    out = [np.asarray(tok)]
    t0 = time.perf_counter()
    for i in range(gen_len - 1):
        logits, cache = step(
            params, cache, tok, jnp.full((b,), prefix + s + i, jnp.int32)
        )
        tok, key = _next_token(logits, temperature, key)
        out.append(np.asarray(tok))
    dt = time.perf_counter() - t0
    return np.concatenate(out, axis=1), dt


class ServeSession:
    """A deployment bound for serving: adapters merged (Algorithm 2 line
    12), substrate backend scope applied around every call.

    Obtained from ``Deployment.serve()``; holds ``params`` in the exact
    ``{"base", "adapters"}`` layout the transformer forward consumes, so
    custom serving loops can also reach in directly (inside
    ``session.scope()``)."""

    def __init__(
        self, deployment, params, options: Optional[dict] = None,
        mesh: Optional[Mesh] = None,
    ):
        self.deployment = deployment
        # the unwrapped single-device tree is kept as the re-mesh source:
        # elastic degradation re-wraps + re-places it on the new mesh
        self._host_params = params
        self.params = params
        self.options = dict(options or {})
        self.mesh = None
        self.shard_stats: Optional[dict] = None
        self._auto_key_calls = 0
        if mesh is not None:
            self.reshard(mesh)

    def reshard(self, mesh: Optional[Mesh]):
        """(Re)bind this session to ``mesh``: wrap every column-shardable
        prepared leaf (``substrate.shard_prepared_for_serve``) and place
        the tree with ``NamedSharding``. ``None`` returns the session to
        the single-device tree. Step functions for the new mesh build
        lazily on first use (the registry keys on mesh)."""
        if mesh is None:
            self.mesh = None
            self.params = self._host_params
            self.shard_stats = None
            return self
        if self.backend != "codes":
            raise ValueError(
                f"mesh serving runs the prepared codes fast path; "
                f"backend={self.backend!r} is single-device"
            )
        wrapped, stats = substrate.shard_prepared_for_serve(
            self._host_params, mesh
        )
        self.params = substrate.place_serve_params(wrapped, mesh)
        self.mesh = mesh
        self.shard_stats = stats
        return self

    def decode_step(self):
        """This session's jitted decode step (mesh-aware). Call inside
        ``scope()``."""
        return decode_step_fn(self.cfg, self.mesh, self.params)

    @property
    def cfg(self):
        return self.deployment.cfg

    @property
    def backend(self) -> str:
        return self.deployment.backend

    def scope(self):
        """The substrate backend scope for this session (RramConfig
        options plumbed automatically, plus any serve-time options like
        ``accum="int8"``). Wrap any custom trace in it."""
        return backend_scope(self.backend, self.cfg, **self.options)

    def _sampling_key(self, temperature: float, key):
        """Derive a sampling key from the deployment key when the caller
        asks for temperature sampling without providing one (it used to
        silently fall back to greedy); reject a key with temperature 0."""
        if temperature > 0 and key is None:
            self._auto_key_calls += 1
            key = jax.random.fold_in(
                self.deployment.program_key, self._auto_key_calls
            )
        _check_sampling_args(temperature, key)
        return key

    def prefill(self, tokens, max_len: int, enc_embeds=None, patch_embeds=None):
        with self.scope():
            return prefill_and_cache(
                self.params, tokens, self.cfg, max_len, enc_embeds,
                mesh=self.mesh, patch_embeds=patch_embeds,
            )

    def generate(
        self, prompt, *, gen_len: int = 16, temperature: float = 0.0,
        enc_embeds=None, patch_embeds=None, key=None,
    ) -> Tuple[np.ndarray, float]:
        """Single-call generation: each prompt row becomes one request on
        a throwaway continuous-batching engine (all admitted at tick 0),
        so this shares the compiled steps and slot bookkeeping with the
        production serving path — including encoder-decoder requests
        (per-slot cross-attention cache lines) and vision-prefix requests
        (``patch_embeds`` (B, P, d))."""
        key = self._sampling_key(temperature, key)
        if self.mesh is not None and (
            enc_embeds is not None or patch_embeds is not None
        ):
            raise ValueError("mesh serving is decoder-only")
        from repro.deploy.engine import ServeEngine

        b, s = prompt.shape
        prefix = 0 if patch_embeds is None else patch_embeds.shape[1]
        src_len = 0 if enc_embeds is None else enc_embeds.shape[1]
        engine = ServeEngine(
            self, max_slots=b, max_len=prefix + s + gen_len, src_len=src_len
        )
        reqs = [
            engine.submit(
                prompt[i], max_new=gen_len, temperature=temperature,
                key=None if key is None else jax.random.fold_in(key, i),
                enc_embeds=None if enc_embeds is None else enc_embeds[i],
                patch_embeds=None if patch_embeds is None else patch_embeds[i],
            )
            for i in range(b)
        ]
        engine.run()
        dt = engine.decode_seconds
        toks = np.stack([np.asarray(r.tokens, np.int32) for r in reqs])
        return toks, dt

    def describe(self) -> str:
        """Startup log line: resident RRAM bytes, SRAM side-car bytes and
        the calibrated-parameter fraction (paper's 2.34% headline)."""
        from repro.core.calibrate import (
            calibrated_fraction, rram_bytes, sram_bytes,
        )

        # byte accounting reads the deployment's true trees, not
        # self.params: serve-time prepared params are padded/fused
        # serving artifacts and would inflate the resident counts
        dep = self.deployment
        kind = "measured resident" if self.backend != "dequant" else "estimated"
        frac = calibrated_fraction(dep.base, dep.adapters)
        return (
            f"backend={self.backend} rram_bytes={rram_bytes(dep.base)}"
            f" ({kind}) sram_bytes={sram_bytes(dep.adapters)}"
            f" calibrated_params={frac:.2%}"
        )
