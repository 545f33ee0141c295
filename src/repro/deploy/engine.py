"""Continuous-batching serve engine over the vectorized decode step.

The paper's deployment story ends here: the RRAM base is frozen, the
DoRA side-cars are merged into the kernel epilogue, and every decoded
token pays one crossbar matmul plus the low-rank epilogue. What this
module adds is the traffic shape of that story — many concurrent
requests with ragged prompts, arriving and finishing at different times,
all advanced by ONE compiled batched ``decode_step``:

* **Slots.** A fixed ``(max_slots, max_len)`` decode cache is allocated
  once. Each in-flight request owns one slot (one batch row); finished
  slots are recycled for queued requests.
* **Per-slot clocks.** ``pos`` is a ``(B,)`` int32 vector — every slot
  sits at its own sequence offset, so ragged prompt lengths and
  mid-stream admission need no padding or lockstep restarts.
* **Chunked admission.** ``submit()`` splits the prompt into fixed-size
  chunks (pow-2 bucketed, masked tail) and advances ONE chunk per engine
  tick interleaved with the decode step, so a long prompt never stalls
  in-flight slots — and the jit cache sees a bounded set of chunk widths
  instead of one program per prompt length. Attention-stack configs
  chunk; SSM/RG-LRU recurrences (``associative_scan`` regrouping is
  length-dependent) keep the fused exact-length prefill. The first token
  is sampled from the last chunk's logits (TTFT recorded per request),
  then the batch-1 cache is scattered into the slot's row
  (``transformer.write_cache_slot``).
* **Encoder-decoder slots.** seamless-style requests carry
  ``enc_embeds``: admission runs the encoder once and freezes per-layer
  cross-attention K/V lines into the slot ("xk"/"xv"), masked per slot
  by ``enc_len`` — decode ticks never touch the encoder again.
* **Vision-prefix slots.** paligemma-style requests carry
  ``patch_embeds``: the ``cfg.vision_tokens`` patch positions are
  prefilled bidirectionally (prefix-LM) ahead of the text chunks, and
  the slot's clock starts at ``P + prompt_len``.
* **Shared prefix cache.** Admission snapshots the batch-1 cache at
  every chunk boundary, keyed by a token-hash chain (seeded with the
  encoder/vision bytes). A later request with the same prefix resumes
  from the snapshot — copy-on-admit, bitwise-identical to a cold
  admission because the snapshot IS the cold computation's intermediate
  state — skipping the shared prompt's prefill entirely (lower TTFT).
* **One jitted step for everyone.** ``step()`` advances ALL active slots
  with a single ``decode_step_fn(cfg)`` call — compiled once per
  ``(cfg, backend)`` in ``deploy.serving`` and reused across requests,
  sessions, and engines (the retrace fix). Inactive slots ride along as
  dead rows: their writes land in recycled cache lines that the per-slot
  validity masks keep invisible to live requests.
* **Unified retirement.** Every request — including one whose FIRST
  token is EOS — retires through ``_finish``; ``first_tokens``,
  ``decode_tokens`` and ``completed`` always satisfy
  ``generated_tokens == first_tokens + decode_tokens`` (asserted in
  tests/test_engine.py).
* **Trace spans.** Each phase of a tick opens a
  ``jax.profiler.TraceAnnotation`` on the host: ``engine.admit`` (one
  admission unit, tagged with the request's ``rid`` and ``slot``),
  ``engine.slot_write`` inside it, ``engine.decode`` (the batched step's
  dispatch) and ``engine.sample`` (the per-slot sampling loop). Under a
  profiler session they share the device trace's clock; without one they
  cost about a microsecond each and add no sync.

Determinism: every row of the batched step computes exactly what a
single-request ``serving.generate`` call computes (row-independent
kernels + per-slot masks + exact-zero masked softmax tails), so engine
output is bitwise-identical to N independent ``generate`` calls —
tests/test_engine.py pins this on the ``dequant`` and ``codes``
backends, ragged + staggered, for every mixer family including
cross-attention and vision-prefix configs.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from typing import Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.deploy import serving

# mixers that support chunked prefill against a live decode cache
_CHUNKABLE = ("attn", "local", "swa")


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record."""

    rid: int
    prompt: np.ndarray               # (s,) int32
    max_new: int
    temperature: float = 0.0
    key: Optional[jax.Array] = None  # advanced as the request samples
    eos_id: Optional[int] = None
    enc_embeds: Optional[np.ndarray] = None    # (s_src, d) [enc-dec]
    patch_embeds: Optional[np.ndarray] = None  # (P, d) [vision prefix]
    tokens: List[int] = dataclasses.field(default_factory=list)
    # with ``submit(keep_logits=True)``: the (V,) f32 logits each token of
    # ``tokens`` was drawn from, copied to the host
    logits: Optional[List[np.ndarray]] = dataclasses.field(
        default=None, repr=False
    )
    done: bool = False
    slot: Optional[int] = None       # None while queued / after retiring
    admitted_tick: Optional[int] = None
    submitted_at: Optional[float] = None  # perf_counter at submit()
    ttft_seconds: Optional[float] = None  # submit -> first token (incl. queue wait)
    prefix_hit_tokens: int = 0       # prompt tokens reused from the prefix cache
    # admission progress (engine-internal, per-slot batch-1 state)
    _cache: Optional[dict] = dataclasses.field(default=None, repr=False)
    _logits: Optional[jax.Array] = dataclasses.field(default=None, repr=False)
    _chain: Optional[list] = dataclasses.field(default=None, repr=False)
    _spans: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list, repr=False
    )
    _vision_pending: bool = dataclasses.field(default=False, repr=False)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def vision_len(self) -> int:
        return 0 if self.patch_embeds is None else int(self.patch_embeds.shape[0])


class ServeEngine:
    """Slot-based continuous-batching scheduler over a ``ServeSession``.

    ``submit()`` admits (or queues) a request; ``step()`` advances every
    admitting slot by one prefill chunk and every active slot by one
    token; ``run()`` drains the queue. Serves every zoo config:
    decoder-only, encoder-decoder (``src_len`` bounds the encoder
    extent), and vision-prefix.
    """

    def __init__(
        self, session, *, max_slots: int = 4, max_len: int = 128,
        src_len: int = 0, prefill_chunk: int = 32, min_bucket: int = 8,
        prefix_cache_entries: int = 16,
    ):
        from repro.models import transformer as T

        self.session = session
        self.cfg = session.cfg
        if self.cfg.encoder_layers and src_len <= 0:
            raise ValueError(
                "encoder-decoder engine needs src_len > 0 (the cross-"
                "attention cache extent; requests may be shorter)"
            )
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.src_len = int(src_len)
        self.prefill_chunk = _pow2_ceil(int(prefill_chunk))
        self.min_bucket = min(_pow2_ceil(int(min_bucket)), self.prefill_chunk)
        self.chunked = all(
            m in _CHUNKABLE for m in self.cfg.mixer_pattern
        )
        self.prefix_cache_entries = int(prefix_cache_entries)
        # chunk-boundary snapshots: hash-chain digest -> (tokens, cache,
        # logits). LRU-capped; lives per engine (cfg+backend+extent fixed).
        self._prefix_cache: "OrderedDict[bytes, tuple]" = OrderedDict()
        with session.scope():
            self.cache = T.init_cache(
                self.cfg, self.max_slots, self.max_len, src_len=self.src_len
            )
        # per-slot clocks / occupancy (host-side scheduler state)
        self.pos = np.zeros(self.max_slots, np.int32)
        self.active = np.zeros(self.max_slots, bool)
        self.last_tok = np.zeros((self.max_slots, 1), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * self.max_slots
        self.pending: Deque[Request] = deque()
        self.tick = 0
        self.decode_seconds = 0.0   # time inside batched decode steps
        self.decode_tokens = 0      # tokens produced by those steps
        self.first_tokens = 0       # tokens sampled from prefill logits
        self.completed = 0          # requests retired (any reason)
        self.prefill_chunks = 0     # chunk/vision admission units run
        self.prefix_lookups = 0
        self.prefix_hits = 0         # full-prompt snapshot hits
        self.prefix_partial_hits = 0  # shared-prefix (partial) hits
        self._next_rid = 0

    @property
    def generated_tokens(self) -> int:
        """Every token handed to a requester, first tokens included."""
        return self.first_tokens + self.decode_tokens

    # -- admission -----------------------------------------------------------

    def submit(
        self, prompt, *, max_new: int = 16, temperature: float = 0.0,
        key: Optional[jax.Array] = None, eos_id: Optional[int] = None,
        enc_embeds=None, patch_embeds=None, keep_logits: bool = False,
    ) -> Request:
        """Enqueue a request; admission starts immediately if a slot is
        free (a single-chunk prompt gets its first token before this
        returns; longer prompts advance one chunk per ``step()``).
        ``prompt`` is a (s,) or (1, s) int token array; ``enc_embeds``
        (s_src, d) for encoder-decoder configs, ``patch_embeds`` (P, d)
        for vision-prefix configs (leading batch dim of 1 accepted).
        ``keep_logits`` records on ``Request.logits`` the logits every
        emitted token was sampled from."""
        serving._check_sampling_args(temperature, key)
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        cfg = self.cfg
        if cfg.encoder_layers:
            if self.session.mesh is not None:
                raise ValueError("mesh serving is decoder-only (no encoder)")
            if enc_embeds is None:
                raise ValueError("encoder-decoder request needs enc_embeds")
            enc_embeds = np.asarray(enc_embeds)
            if enc_embeds.ndim == 3:
                enc_embeds = enc_embeds[0]
            if enc_embeds.shape[0] > self.src_len:
                raise ValueError(
                    f"enc_embeds length {enc_embeds.shape[0]} exceeds engine "
                    f"src_len ({self.src_len})"
                )
        elif enc_embeds is not None:
            raise ValueError("enc_embeds passed to a decoder-only config")
        if patch_embeds is not None:
            if not cfg.vision_tokens:
                raise ValueError(
                    "patch_embeds passed to a config without vision_tokens"
                )
            if self.session.mesh is not None:
                raise ValueError("mesh serving has no vision-prefix path")
            patch_embeds = np.asarray(patch_embeds)
            if patch_embeds.ndim == 3:
                patch_embeds = patch_embeds[0]
            if patch_embeds.shape[0] != cfg.vision_tokens:
                raise ValueError(
                    f"expected {cfg.vision_tokens} vision tokens, got "
                    f"{patch_embeds.shape[0]}"
                )
        prefix = 0 if patch_embeds is None else patch_embeds.shape[0]
        if prefix + prompt.size + max_new > self.max_len:
            raise ValueError(
                f"prompt ({prefix + prompt.size}) + max_new ({max_new}) "
                f"exceeds engine max_len ({self.max_len})"
            )
        req = Request(
            rid=self._next_rid, prompt=prompt, max_new=int(max_new),
            temperature=float(temperature), key=key, eos_id=eos_id,
            enc_embeds=enc_embeds, patch_embeds=patch_embeds,
            submitted_at=time.perf_counter(),
            logits=[] if keep_logits else None,
        )
        self._next_rid += 1
        self.pending.append(req)
        self._admit_pending()
        return req

    def _free_slots(self) -> List[int]:
        return [i for i in range(self.max_slots) if self.slot_req[i] is None]

    def _admit_pending(self) -> None:
        """Assign free slots to queued requests and run each new slot's
        first admission unit (a retired-at-first-token request frees its
        slot for the next queued request immediately)."""
        while self.pending:
            free = self._free_slots()
            if not free:
                return
            slot = free[0]
            req = self.pending.popleft()
            self._start_admission(req, slot)
            self._advance_admission(slot)

    def _bucket(self, n: int) -> int:
        """Pow-2 chunk bucket (masked tail) in
        [min_bucket, prefill_chunk] — the bounded set of chunk widths the
        jitted chunk step ever sees."""
        b = self.min_bucket
        while b < n:
            b *= 2
        return b

    def _spans(self, start: int, n: int) -> List[Tuple[int, int]]:
        return [
            (a, min(a + self.prefill_chunk, n))
            for a in range(start, n, self.prefill_chunk)
        ]

    def _start_admission(self, req: Request, slot: int) -> None:
        """Bind ``req`` to ``slot`` and stage its admission plan: prefix
        cache lookup, then encoder / vision / chunk units as needed."""
        from repro.models import transformer as T

        req.slot = slot
        self.slot_req[slot] = req
        cfg = self.cfg
        n = req.prompt_len
        req._chain = self._hash_chain(req)
        hit = self._prefix_lookup(req)
        if not self.chunked:
            # SSM/RG-LRU: fused exact-length prefill (recurrences do not
            # chunk bitwise); the prefix cache only serves full hits.
            if hit == n:
                return
            with self.session.scope():
                req._logits, req._cache = serving.prefill_and_cache(
                    self.session.params, jnp.asarray(req.prompt)[None, :],
                    cfg, self.max_len, mesh=self.session.mesh,
                )
            self._store_prefix(req, n)
            return
        if hit == n:
            return  # full snapshot hit: cache + logits already staged
        if req._cache is None:  # no partial hit to resume from
            with self.session.scope():
                req._cache = T.init_cache(
                    cfg, 1, self.max_len, src_len=self.src_len
                )
                if cfg.encoder_layers:
                    req._cache = serving.encode_fn(cfg, self.session.mesh)(
                        self.session.params, req._cache,
                        jnp.asarray(req.enc_embeds)[None],
                    )
            req._vision_pending = req.patch_embeds is not None
        req._spans = self._spans(hit, n)

    def _advance_admission(self, slot: int) -> None:
        """Run ONE admission unit (vision prefix or one prompt chunk) for
        the slot; finalize (sample the first token) when the plan is
        exhausted."""
        req = self.slot_req[slot]
        if req is None or self.active[slot] or req.done:
            return
        with jax.profiler.TraceAnnotation(
            "engine.admit", rid=req.rid, slot=int(slot)
        ):
            cfg = self.cfg
            if req._vision_pending:
                with self.session.scope():
                    vision = serving.prefill_vision_fn(cfg, self.session.mesh)
                    req._cache = vision(
                        self.session.params,
                        jnp.asarray(req.patch_embeds)[None], req._cache,
                        self.max_len,
                    )
                req._vision_pending = False
                self.prefill_chunks += 1
                if req._spans:
                    return  # text chunks continue on the next tick
            elif req._spans:
                a, b_ = req._spans.pop(0)
                with self.session.scope():
                    req._logits, req._cache = self._chunk_call(
                        req._cache, req.prompt, a, b_, req.vision_len
                    )
                self.prefill_chunks += 1
                self._store_prefix(req, b_)
                if req._spans:
                    return
            self._finalize_admission(slot, req)

    def _chunk_call(self, cache, prompt, a, b_, vision_len):
        """One bucketed chunk step: tokens [a, b_) at absolute positions
        ``vision_len + [a, b_)``, zero-padded to the pow-2 bucket."""
        n = b_ - a
        bucket = self._bucket(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prompt[a:b_]
        fn = serving.prefill_chunk_fn(
            self.cfg, self.session.mesh, self.session.params
        )
        return fn(
            self.session.params, jnp.asarray(toks), cache,
            jnp.asarray([vision_len + a], jnp.int32),
            jnp.asarray([n], jnp.int32), self.max_len, 0,
        )

    def _finalize_admission(self, slot: int, req: Request) -> None:
        """Sample the first token from the admission logits and either
        activate the slot for decode ticks or retire immediately (first
        token is EOS / max_new == 1) — same accounting either way."""
        from repro.models import transformer as T

        tok, req.key = serving._next_token(
            req._logits, req.temperature, req.key
        )
        first = int(np.asarray(tok)[0, 0])
        _keep_logits(req, req._logits)
        req.ttft_seconds = time.perf_counter() - req.submitted_at
        req.tokens.append(first)
        req.admitted_tick = self.tick
        self.first_tokens += 1
        one = req._cache
        req._cache = None
        req._logits = None
        req._chain = None
        if req.max_new <= 1 or (req.eos_id is not None and first == req.eos_id):
            self._finish(req, slot)  # nothing to decode — recycle the slot
            return
        with self.session.scope(), jax.profiler.TraceAnnotation(
            "engine.slot_write"
        ):
            self.cache = T.write_cache_slot(self.cache, one, slot)
        self.active[slot] = True
        self.pos[slot] = req.vision_len + req.prompt_len  # next write position
        self.last_tok[slot, 0] = first

    # -- prefix cache --------------------------------------------------------

    def _hash_chain(self, req: Request) -> List[bytes]:
        """chain[k] identifies the request's first k prompt tokens (plus
        the full encoder/vision inputs, which are part of position 0's
        context) — the snapshot key for a cache state with exactly k
        prompt tokens admitted."""
        h = hashlib.sha1(b"rimc-prefix-v1")
        if req.enc_embeds is not None:
            h.update(np.ascontiguousarray(req.enc_embeds).tobytes())
        if req.patch_embeds is not None:
            h.update(np.ascontiguousarray(req.patch_embeds).tobytes())
        chain = [h.digest()]
        for t in req.prompt:
            h2 = hashlib.sha1(chain[-1])
            h2.update(int(t).to_bytes(8, "little", signed=True))
            chain.append(h2.digest())
        return chain

    def _prefix_lookup(self, req: Request) -> int:
        """Longest stored snapshot matching this request's prefix. On a
        hit, stage the snapshot's cache + boundary logits on the request
        and return the number of prompt tokens covered (0 = cold)."""
        if self.prefix_cache_entries <= 0:
            return 0
        self.prefix_lookups += 1
        n = req.prompt_len
        candidates = range(n, 0, -1) if self.chunked else (n,)
        for k in candidates:
            entry = self._prefix_cache.get(req._chain[k])
            if entry is None:
                continue
            toks, cache, logits = entry
            if toks.shape[0] != k or not np.array_equal(toks, req.prompt[:k]):
                continue  # hash collision — treat as miss
            self._prefix_cache.move_to_end(req._chain[k])
            req._cache = cache
            req._logits = logits
            req.prefix_hit_tokens = k
            if k == n:
                self.prefix_hits += 1
            else:
                self.prefix_partial_hits += 1
            return k
        return 0

    def _store_prefix(self, req: Request, k: int) -> None:
        """Snapshot the admission state after k prompt tokens. The jax
        arrays are immutable, so the snapshot stays valid while later
        chunks build new trees on top of it."""
        if self.prefix_cache_entries <= 0:
            return
        key = req._chain[k]
        if key in self._prefix_cache:
            self._prefix_cache.move_to_end(key)
            return
        self._prefix_cache[key] = (
            req.prompt[:k].copy(), req._cache, req._logits
        )
        while len(self._prefix_cache) > self.prefix_cache_entries:
            self._prefix_cache.popitem(last=False)

    # -- decode tick ---------------------------------------------------------

    def step(self) -> bool:
        """Admit what fits, advance every admitting slot by one prefill
        unit, then advance every active slot by one token with a single
        batched ``decode_step``. Returns False when there is nothing left
        to do (no active or admitting slots, empty queue)."""
        self._admit_pending()
        for slot in range(self.max_slots):
            req = self.slot_req[slot]
            if req is not None and not self.active[slot] and not req.done:
                self._advance_admission(slot)
        if not self.active.any():
            busy = bool(self.pending) or any(
                r is not None and not r.done for r in self.slot_req
            )
            if busy:
                self.tick += 1  # an admission-only tick still advances time
            return busy
        t0 = time.perf_counter()
        with self.session.scope(), jax.profiler.TraceAnnotation(
            "engine.decode"
        ):
            # fetch INSIDE the scope: the registry key includes the
            # active backend name, and codes vs codes_adc sessions share
            # identical param avals — a scope-blind fetch would let one
            # hit the other's trace
            step = self.session.decode_step()
            logits, self.cache = step(
                self.session.params, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos),
            )
        n_live = int(self.active.sum())
        # the host waits here: for the step's logits, then slot by slot
        with jax.profiler.TraceAnnotation("engine.sample"):
            for slot in np.flatnonzero(self.active):
                req = self.slot_req[slot]
                tok, req.key = serving._next_token(
                    logits[slot : slot + 1], req.temperature, req.key
                )
                t = int(np.asarray(tok)[0, 0])
                _keep_logits(req, logits[slot : slot + 1])
                req.tokens.append(t)
                self.pos[slot] += 1
                self.last_tok[slot, 0] = t
                hit_eos = req.eos_id is not None and t == req.eos_id
                out_of_room = int(self.pos[slot]) + 1 >= self.max_len
                if len(req.tokens) >= req.max_new or hit_eos or out_of_room:
                    self._finish(req, slot)
        self.decode_seconds += time.perf_counter() - t0
        self.decode_tokens += n_live
        self.tick += 1
        return True

    def _finish(self, req: Request, slot: Optional[int] = None) -> None:
        """The single retirement path — first-token EOS, max_new, EOS
        mid-stream, and out-of-room all come through here, so the
        counters stay consistent across every exit."""
        req.done = True
        req.slot = None
        self.completed += 1
        if slot is not None:
            self.slot_req[slot] = None
            self.active[slot] = False

    def run(self) -> None:
        """Drain: admit + step until every submitted request retired."""
        while self.step():
            pass

    # -- elastic degradation -------------------------------------------------

    def remesh(self, new_mesh=None, *, n_failed_hosts: int = 1):
        """A host dropped mid-serve: re-bind the session to the degraded
        mesh and rebuild the decode cache by replaying every in-flight
        slot from its deterministic lifecycle — the prompt plus the
        already-emitted token stream. Returns the ``ElasticPlan``.

        Without an explicit ``new_mesh``, the plan derives it from the
        session's current mesh by dropping ``n_failed_hosts`` data-axis
        rows (``launch.mesh.make_elastic_mesh``); the model axis is
        untouched, so the wrap policy reshards params identically and
        replayed decode is bitwise the undisturbed engine's.

        Replay is per-slot batch-1 through the SAME admission machinery
        the slot originally ran (chunked prefill for attention stacks,
        fused prefill otherwise — the two families are not
        bitwise-interchangeable), then each recorded token re-fed through
        single decode steps at its original position. Host scheduler
        state — per-slot clocks, last sampled token, the request's
        advanced PRNG key — carries over untouched; nothing is
        resampled."""
        from repro.launch.mesh import make_elastic_mesh
        from repro.models import transformer as T
        from repro.runtime.fault import ElasticPlan

        mesh = self.session.mesh
        if new_mesh is None:
            if mesh is None:
                raise ValueError(
                    "remesh needs either an explicit new_mesh or a session "
                    "already bound to a mesh to degrade"
                )
            plan = ElasticPlan.plan(
                n_failed_hosts, self.tick,
                rows=int(mesh.shape["data"]), cols=int(mesh.shape["model"]),
            )
            new_mesh = make_elastic_mesh(n_failed_hosts, base_mesh=mesh)
        else:
            dropped = 0
            if mesh is not None and "data" in mesh.shape:
                dropped = int(mesh.shape["data"]) - int(
                    new_mesh.shape.get("data", 1)
                )
            plan = ElasticPlan(
                failed_hosts=max(dropped, 0),
                new_mesh_shape=tuple(new_mesh.devices.shape),
                restore_step=self.tick,
                notes="explicit re-mesh",
            )
        self.session.reshard(new_mesh)
        with self.session.scope():
            self.cache = T.init_cache(
                self.cfg, self.max_slots, self.max_len, src_len=self.src_len
            )
            step = self.session.decode_step()
            for slot in np.flatnonzero(self.active):
                req = self.slot_req[slot]
                one = self._replay_admission(req)
                # re-feed all but the pending last token: token j was
                # consumed at position vision_len + prompt_len + j; the
                # engine's last_tok/pos still point at the un-issued write
                pos0 = req.vision_len + req.prompt_len
                for j, t in enumerate(req.tokens[:-1]):
                    _, one = step(
                        self.session.params, one,
                        jnp.asarray([[t]], jnp.int32),
                        jnp.asarray([pos0 + j], jnp.int32),
                    )
                self.cache = T.write_cache_slot(self.cache, one, slot)
        return plan

    def _replay_admission(self, req: Request):
        """Rebuild a slot's post-admission batch-1 cache, bitwise equal
        to what admission originally produced (deterministic; prefix-
        cache hits change nothing because a snapshot IS the cold state)."""
        from repro.models import transformer as T

        cfg = self.cfg
        if not self.chunked:
            _, one = serving.prefill_and_cache(
                self.session.params, jnp.asarray(req.prompt)[None, :],
                cfg, self.max_len, mesh=self.session.mesh,
            )
            return one
        one = T.init_cache(cfg, 1, self.max_len, src_len=self.src_len)
        if cfg.encoder_layers:
            one = serving.encode_fn(cfg, self.session.mesh)(
                self.session.params, one, jnp.asarray(req.enc_embeds)[None]
            )
        if req.patch_embeds is not None:
            one = serving.prefill_vision_fn(cfg, self.session.mesh)(
                self.session.params, jnp.asarray(req.patch_embeds)[None],
                one, self.max_len,
            )
        for a, b_ in self._spans(0, req.prompt_len):
            _, one = self._chunk_call(one, req.prompt, a, b_, req.vision_len)
        return one

    # -- introspection -------------------------------------------------------

    @property
    def num_active(self) -> int:
        return int(self.active.sum())

    def compile_count(self) -> int:
        """Compiled-computation count for this engine's (cfg, backend)
        step functions — flat across requests once warm (the retrace
        regression metric)."""
        with self.session.scope():
            return serving.compile_count(self.cfg, self.session.mesh)

    def stats(self) -> dict:
        return {
            "ticks": self.tick,
            "decode_seconds": self.decode_seconds,
            "decode_tokens": self.decode_tokens,
            "first_tokens": self.first_tokens,
            "generated_tokens": self.generated_tokens,
            "completed": self.completed,
            "prefill_chunks": self.prefill_chunks,
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_partial_hits": self.prefix_partial_hits,
            "decode_tok_per_s": (
                self.decode_tokens / self.decode_seconds
                if self.decode_seconds > 0 else float("nan")
            ),
            "compile_count": self.compile_count(),
        }


def _keep_logits(req: Request, logits) -> None:
    """Append the last position of (1, s, V) ``logits`` to ``req.logits``
    when the request asked for them."""
    if req.logits is not None:
        req.logits.append(np.asarray(logits[0, -1], np.float32))


def _pow2_ceil(n: int) -> int:
    if n < 1:
        raise ValueError(f"need a positive size, got {n}")
    b = 1
    while b < n:
        b *= 2
    return b
