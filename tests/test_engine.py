"""Continuous-batching engine: ragged/staggered bitwise parity with
per-request ``generate``, the retrace fix (zero recompilations after the
first call), fused-prefill parity with the per-token loop, and the
sampling-intent fixes (ISSUE 4 acceptance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.deploy import Deployment, ServeEngine, serving
from repro.models import transformer as T


def _reference(session, prompt, gen_len, temperature=0.0, key=None,
               enc_embeds=None, patch_embeds=None):
    """Per-request reference: the single-stream generate loop, one call
    per prompt (batch 1) — what the engine must reproduce bitwise."""
    with session.scope():
        toks, _ = serving.generate(
            session.params, jnp.asarray(prompt, jnp.int32)[None, :],
            session.cfg, gen_len=gen_len, temperature=temperature, key=key,
            enc_embeds=None if enc_embeds is None else enc_embeds[None],
            patch_embeds=None if patch_embeds is None else patch_embeds[None],
        )
    return list(np.asarray(toks)[0])


def _ragged_staggered_check(arch, backend, *, max_len, prompt_lens, gen_len,
                            temperature=0.9, enc_lens=None, vision=False,
                            **engine_kw):
    cfg = get_arch(arch).smoke
    session = Deployment.program(cfg, 0, backend=backend).serve()
    prompts = [
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(50 + i), (n,), 0, cfg.vocab
        ))
        for i, n in enumerate(prompt_lens)
    ]
    encs = [None] * len(prompts)
    if enc_lens is not None:
        encs = [
            np.asarray(jax.random.normal(
                jax.random.PRNGKey(200 + i), (n, cfg.d_model), cfg.dtype
            ))
            for i, n in enumerate(enc_lens)
        ]
        engine_kw.setdefault("src_len", max(enc_lens))
    patches = [None] * len(prompts)
    if vision:
        patches = [
            np.asarray(jax.random.normal(
                jax.random.PRNGKey(300 + i), (cfg.vision_tokens, cfg.d_model),
                cfg.dtype,
            ))
            for i in range(len(prompts))
        ]
    keys = [jax.random.PRNGKey(100 + i) for i in range(len(prompts))]
    refs = [
        _reference(session, p, gen_len, temperature, k,
                   enc_embeds=e, patch_embeds=v)
        for p, k, e, v in zip(prompts, keys, encs, patches)
    ]
    # fewer slots than requests, admissions at different ticks -> the
    # engine must interleave rows at different clocks and recycle slots
    engine = ServeEngine(session, max_slots=2, max_len=max_len, **engine_kw)
    reqs = []
    for i, (p, k, e, v) in enumerate(zip(prompts, keys, encs, patches)):
        reqs.append(
            engine.submit(p, max_new=gen_len, temperature=temperature, key=k,
                          enc_embeds=e, patch_embeds=v)
        )
        engine.step()
        engine.step()
    engine.run()
    for i, (req, ref) in enumerate(zip(reqs, refs)):
        assert req.done
        assert req.tokens == ref, f"request {i}: {req.tokens} != {ref}"
    assert engine.generated_tokens == sum(len(r.tokens) for r in reqs)
    return engine, reqs


@pytest.mark.parametrize("backend", ["dequant", "codes"])
def test_ragged_staggered_parity_dense(backend):
    """Engine output is bitwise-identical to N independent generate
    calls — ragged prompts, staggered admission, both backends."""
    _ragged_staggered_check(
        "qwen3_1_7b", backend, max_len=32,
        prompt_lens=[5, 11, 3], gen_len=6,
    )


@pytest.mark.parametrize("backend", ["dequant", "codes"])
def test_ragged_parity_sliding_window_wraparound(backend):
    """mixtral smoke (window 16): prompts + generation cross the rolling
    buffer boundary, exercising the vectorized per-slot wrap-around in
    ``_cache_mask``/``_cache_write``."""
    _ragged_staggered_check(
        "mixtral_8x22b", backend, max_len=40,
        prompt_lens=[14, 20], gen_len=8,
    )


def test_ragged_parity_mla():
    """deepseek-v2 smoke: MLA latent cache (c_kv + shared rope key) on
    the codes backend."""
    _ragged_staggered_check(
        "deepseek_v2_lite_16b", "codes", max_len=32,
        prompt_lens=[9, 4], gen_len=5,
    )


def test_slot_recycling_and_eos():
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (6,), 0, cfg.vocab)
    )
    key = jax.random.PRNGKey(7)
    # The smoke model ties lm_head to a unit-variance embedding, so the
    # logit of the token just fed in leads by ~d_model against ~sqrt(d_model)
    # and a temperature-1 stream repeats its first token forever. Sampling
    # at temperature d_model flattens that lead, so the stream draws
    # near-uniformly over the vocab and new tokens appear mid-stream.
    temperature = float(cfg.d_model)
    ref = _reference(session, prompt, 8, temperature=temperature, key=key)
    # eos = the first token value whose FIRST occurrence is at index >= 2:
    # the engine must stop there (token included) and free the slot for
    # the queued second request
    j = next((i for i in range(2, len(ref)) if ref[i] not in ref[:i]), None)
    assert j is not None, (
        f"premise: the reference stream {ref} has no token that first "
        "appears at index >= 2, so no mid-stream EOS can be chosen"
    )
    engine = ServeEngine(session, max_slots=1, max_len=24)
    r1 = engine.submit(
        prompt, max_new=8, temperature=temperature, key=key, eos_id=ref[j]
    )
    r2 = engine.submit(prompt + 1, max_new=3)
    assert r2.slot is None and engine.pending  # queued: no free slot
    engine.run()
    assert r1.done and r1.tokens == ref[: j + 1]
    assert r2.done and len(r2.tokens) == 3
    assert engine.num_active == 0 and not engine.pending


def test_second_generate_call_triggers_zero_new_compilations():
    """The retrace bug: every request used to re-wrap jax.jit and
    recompile. The registry compiles on the first call only."""
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, cfg.vocab)
    session.generate(prompt, gen_len=4)
    with session.scope():
        warm = serving.compile_count(cfg)
    assert warm > 0
    for _ in range(3):
        session.generate(prompt, gen_len=4)
    with session.scope():
        assert serving.compile_count(cfg) == warm
    # the engine path stays warm too: same-shape resubmission compiles 0
    engine = ServeEngine(session, max_slots=2, max_len=12)
    engine.submit(prompt[0], max_new=4)
    engine.run()
    warm = engine.compile_count()
    engine.submit(prompt[0], max_new=4)
    engine.run()
    assert engine.compile_count() == warm


def test_compile_count_warm_parity_codes_vs_dequant():
    """The codes backend compiles exactly as many step programs as the
    dequant reference for the same request mix. It used to compile twice
    as many: ``backend_scope("dequant")`` was a nullcontext, so both
    backends shared one registry entry keyed on the ambient default and
    each clobbered the other's trace cache."""
    import dataclasses

    cfg = dataclasses.replace(
        get_arch("qwen3_1_7b").smoke, name="qwen3-smoke-warm-parity"
    )
    counts = {}
    for backend in ("dequant", "codes"):
        session = Deployment.program(cfg, 0, backend=backend).serve()
        for plen in (4, 7, 4):
            prompt = jax.random.randint(
                jax.random.PRNGKey(plen), (1, plen), 0, cfg.vocab
            )
            session.generate(prompt, gen_len=3)
        with session.scope():
            counts[backend] = serving.compile_count(cfg)
    assert counts["codes"] == counts["dequant"] > 0


@pytest.mark.parametrize(
    "arch_id",
    ["qwen3_1_7b", "falcon_mamba_7b", "recurrentgemma_9b",
     "deepseek_v2_lite_16b", "mixtral_8x22b"],
)
def test_fused_prefill_matches_token_loop(arch_id):
    """Fused full-sequence prefill == per-token decode_step loop: same
    last-position logits (up to the SSM associative-vs-sequential scan
    rounding) and an identical greedy continuation from either cache."""
    cfg = get_arch(arch_id).smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    p = {"base": params["base"],
         "adapters": T._empty_adapters(params["adapters"])}
    s, max_len = 9, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, s), 0, cfg.vocab)
    cache_l = T.init_cache(cfg, 2, max_len)
    for i in range(s):
        logits_l, cache_l = T.decode_step(
            p, cache_l, toks[:, i : i + 1], jnp.int32(i), cfg
        )
    logits_f, cache_f = T.prefill(p, toks, cfg, max_len)
    np.testing.assert_allclose(
        np.asarray(logits_l[:, -1], np.float32),
        np.asarray(logits_f[:, -1], np.float32),
        rtol=0.15, atol=0.15,
    )
    tl = jnp.argmax(logits_l[:, -1], -1)[:, None].astype(jnp.int32)
    tf = jnp.argmax(logits_f[:, -1], -1)[:, None].astype(jnp.int32)
    for i in range(4):
        assert bool((tl == tf).all())
        logits_l, cache_l = T.decode_step(p, cache_l, tl, jnp.int32(s + i), cfg)
        logits_f, cache_f = T.decode_step(p, cache_f, tf, jnp.int32(s + i), cfg)
        tl = jnp.argmax(logits_l[:, -1], -1)[:, None].astype(jnp.int32)
        tf = jnp.argmax(logits_f[:, -1], -1)[:, None].astype(jnp.int32)


def test_vector_pos_matches_scalar_pos():
    """(B,) per-slot clocks with equal entries == the legacy scalar pos."""
    cfg = get_arch("qwen3_1_7b").smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    p = {"base": params["base"],
         "adapters": T._empty_adapters(params["adapters"])}
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab)
    c1 = T.init_cache(cfg, 2, 8)
    c2 = T.init_cache(cfg, 2, 8)
    for i in range(4):
        l1, c1 = T.decode_step(p, c1, toks[:, i : i + 1], jnp.int32(i), cfg)
        l2, c2 = T.decode_step(
            p, c2, toks[:, i : i + 1], jnp.full((2,), i, jnp.int32), cfg
        )
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_temperature_without_key_samples():
    """temperature > 0 without a key must sample (deriving a key from
    the deployment key), not silently argmax."""
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab)
    t1, _ = session.generate(prompt, gen_len=2, temperature=8.0)
    t2, _ = session.generate(prompt, gen_len=2, temperature=8.0)
    greedy, _ = session.generate(prompt, gen_len=2)
    # near-uniform sampling: the derived keys differ per call, and at
    # least one draw differs from the argmax path
    assert not np.array_equal(t1, t2)
    assert not (np.array_equal(t1, greedy) and np.array_equal(t2, greedy))


def test_key_with_zero_temperature_raises():
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, cfg.vocab)
    with pytest.raises(ValueError, match="greedily"):
        session.generate(prompt, gen_len=2, key=jax.random.PRNGKey(0))
    engine = ServeEngine(session, max_slots=1, max_len=8)
    with pytest.raises(ValueError, match="greedily"):
        engine.submit(prompt[0], max_new=2, key=jax.random.PRNGKey(0))


def test_engine_submit_validation():
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    engine = ServeEngine(session, max_slots=1, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        engine.submit(np.zeros(6, np.int32), max_new=4)
    with pytest.raises(ValueError, match="decoder-only"):
        engine.submit(np.zeros(2, np.int32), max_new=2,
                      enc_embeds=np.zeros((4, cfg.d_model), np.float32))
    with pytest.raises(ValueError, match="vision_tokens"):
        engine.submit(np.zeros(2, np.int32), max_new=2,
                      patch_embeds=np.zeros((4, cfg.d_model), np.float32))
    enc_cfg = get_arch("seamless_m4t_large_v2").smoke
    enc_session = Deployment.program(enc_cfg, 0).serve()
    with pytest.raises(ValueError, match="src_len"):
        ServeEngine(enc_session)  # enc-dec engine needs the encoder extent
    enc_engine = ServeEngine(enc_session, max_slots=1, max_len=16, src_len=4)
    with pytest.raises(ValueError, match="enc_embeds"):
        enc_engine.submit(np.zeros(2, np.int32), max_new=2)
    with pytest.raises(ValueError, match="src_len"):
        enc_engine.submit(
            np.zeros(2, np.int32), max_new=2,
            enc_embeds=np.zeros((6, enc_cfg.d_model), np.float32),
        )
    vis_cfg = get_arch("paligemma_3b").smoke
    vis_session = Deployment.program(vis_cfg, 0).serve()
    vis_engine = ServeEngine(vis_session, max_slots=1, max_len=16)
    with pytest.raises(ValueError, match="vision tokens"):
        vis_engine.submit(
            np.zeros(2, np.int32), max_new=2,
            patch_embeds=np.zeros((3, vis_cfg.d_model), np.float32),
        )
    # the vision prefix counts against max_len: 8 + 5 + 4 > 16
    with pytest.raises(ValueError, match="max_len"):
        vis_engine.submit(
            np.zeros(5, np.int32), max_new=4,
            patch_embeds=np.zeros(
                (vis_cfg.vision_tokens, vis_cfg.d_model), np.float32
            ),
        )


@pytest.mark.parametrize("backend", ["dequant", "codes"])
def test_ragged_staggered_parity_encdec(backend):
    """seamless smoke through the engine: per-slot cross-attention cache
    lines, ragged encoder lengths masked per slot by enc_len — bitwise
    vs per-request generate."""
    _ragged_staggered_check(
        "seamless_m4t_large_v2", backend, max_len=24,
        prompt_lens=[5, 9, 3], gen_len=5, enc_lens=[3, 4, 2],
        prefill_chunk=4, min_bucket=4,
    )


@pytest.mark.parametrize("backend", ["dequant", "codes"])
def test_ragged_staggered_parity_vision(backend):
    """paligemma smoke through the engine: image-prefix admission (the
    8 patch positions prefill bidirectionally ahead of the text chunks,
    clocks offset by vision_tokens) — bitwise vs generate."""
    _ragged_staggered_check(
        "paligemma_3b", backend, max_len=32,
        prompt_lens=[6, 10], gen_len=5, vision=True,
        prefill_chunk=4, min_bucket=4,
    )


def test_chunked_prefill_matches_fused_admission():
    """Chunk width is a scheduling knob, not a numerics knob: the same
    prompt admitted through 2-token chunks and through one fused span
    generates identical tokens."""
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (11,), 0, cfg.vocab)
    )
    outs = []
    for chunk in (2, 16):
        engine = ServeEngine(
            session, max_slots=1, max_len=24, prefill_chunk=chunk,
            min_bucket=2, prefix_cache_entries=0,
        )
        req = engine.submit(prompt, max_new=6)
        engine.run()
        outs.append(req.tokens)
    assert outs[0] == outs[1]


def test_prefix_cache_hit_is_bitwise_and_counted():
    """A request whose prompt shares a stored prefix resumes from the
    snapshot — tokens bitwise-identical to a cold admission, hits
    visible in stats(), and full hits skip prefill chunks entirely."""
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    shared = np.asarray(
        jax.random.randint(jax.random.PRNGKey(8), (8,), 0, cfg.vocab)
    )
    tail = np.asarray(
        jax.random.randint(jax.random.PRNGKey(9), (5,), 0, cfg.vocab)
    )
    long = np.concatenate([shared, tail])
    cold_refs = {
        n: _reference(session, p, 5) for n, p in
        [("shared", shared), ("long", long)]
    }
    engine = ServeEngine(
        session, max_slots=1, max_len=32, prefill_chunk=4, min_bucket=4
    )
    r1 = engine.submit(shared, max_new=5)
    engine.run()
    assert r1.tokens == cold_refs["shared"] and r1.prefix_hit_tokens == 0
    chunks_cold = engine.prefill_chunks
    # exact resubmission: full snapshot hit, zero prefill chunks run
    r2 = engine.submit(shared, max_new=5)
    engine.run()
    assert r2.tokens == cold_refs["shared"]
    assert r2.prefix_hit_tokens == len(shared)
    assert engine.prefix_hits == 1 and engine.prefill_chunks == chunks_cold
    # shared system prompt + new tail: partial hit at the chunk boundary
    r3 = engine.submit(long, max_new=5)
    engine.run()
    assert r3.tokens == cold_refs["long"]
    assert r3.prefix_hit_tokens == len(shared)
    assert engine.prefix_partial_hits == 1
    st = engine.stats()
    assert st["prefix_lookups"] == 3 and st["prefix_hits"] == 1


def test_prefix_cache_full_hit_nonchunked():
    """SSM stacks don't chunk (recurrence regrouping), but an exact
    prompt resubmission still reuses the fused-prefill snapshot."""
    cfg = get_arch("falcon_mamba_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (7,), 0, cfg.vocab)
    )
    ref = _reference(session, prompt, 4)
    engine = ServeEngine(session, max_slots=1, max_len=16)
    r1 = engine.submit(prompt, max_new=4)
    engine.run()
    r2 = engine.submit(prompt, max_new=4)
    engine.run()
    assert r1.tokens == ref and r2.tokens == ref
    assert engine.prefix_hits == 1 and r2.prefix_hit_tokens == 7


def test_chunk_bucketing_pins_compile_ceiling():
    """Pow-2 chunk buckets bound the jit cache: once the bucket set is
    warm, NEW ragged prompt lengths compile nothing."""
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    engine = ServeEngine(
        session, max_slots=2, max_len=64, prefill_chunk=8, min_bucket=4,
        prefix_cache_entries=0,
    )

    def toks(n, seed):
        return np.asarray(
            jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab)
        )

    # warm the full bucket set {4, 8}: a sub-bucket tail and full chunks
    for n in (3, 12):
        engine.submit(toks(n, n), max_new=2)
    engine.run()
    warm = engine.compile_count()
    assert warm > 0
    # six unseen prompt lengths -> same buckets, zero new programs
    for n in (2, 5, 7, 9, 17, 23):
        engine.submit(toks(n, 100 + n), max_new=2)
    engine.run()
    assert engine.compile_count() == warm


def test_engine_accounting_unified_retirement():
    """first_tokens/decode_tokens/completed stay consistent across every
    exit path — max_new=1, first-token EOS, and normal retirement all
    satisfy generated_tokens == first + decode == sum(emitted)."""
    cfg = get_arch("qwen3_1_7b").smoke
    session = Deployment.program(cfg, 0).serve()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (5,), 0, cfg.vocab)
    )
    ref = _reference(session, prompt, 6)
    engine = ServeEngine(session, max_slots=2, max_len=16)
    r_one = engine.submit(prompt, max_new=1)          # retires at admission
    r_eos = engine.submit(prompt, max_new=6, eos_id=ref[0])  # first tok EOS
    r_full = engine.submit(prompt, max_new=6)
    engine.run()
    assert r_one.done and r_one.tokens == ref[:1]
    assert r_eos.done and r_eos.tokens == ref[:1]
    assert r_full.done and r_full.tokens == ref
    assert r_one.ttft_seconds is not None and r_eos.ttft_seconds is not None
    st = engine.stats()
    emitted = sum(len(r.tokens) for r in (r_one, r_eos, r_full))
    assert st["first_tokens"] == 3
    assert st["completed"] == 3
    assert st["generated_tokens"] == st["first_tokens"] + st["decode_tokens"]
    assert st["generated_tokens"] == emitted


def test_keep_logits_match_the_forward_reference():
    """``submit(keep_logits=True)`` hands back the logits each emitted
    token was drawn from. Through chunked admission and ragged batched
    decode they agree with a plain ``T.forward`` over prompt + emitted
    tokens (dequant backend, precision "highest"; 0.05 is the bound
    tests/test_backends.py holds codes vs dequant to), and each greedy
    token is their argmax. Without the option nothing is kept."""
    from repro import substrate

    cfg = get_arch("qwen3_1_7b").smoke
    dep = Deployment.program(cfg, 0, backend="codes")
    session = dep.serve()
    engine = ServeEngine(session, max_slots=2, max_len=64, prefill_chunk=16)
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(60 + i), (n,), 0,
                                      cfg.vocab))
        for i, n in enumerate((5, 37, 50))
    ]
    reqs = [engine.submit(p, max_new=6, keep_logits=True) for p in prompts]
    plain = engine.submit(prompts[0], max_new=2)
    engine.run()
    assert plain.done and plain.logits is None
    fwd = jax.jit(lambda p, t: T.forward(p, {"tokens": t}, cfg))
    for req in reqs:
        assert req.done and len(req.logits) == len(req.tokens) == 6
        got = np.stack(req.logits).astype(np.float64)
        assert list(got.argmax(-1)) == req.tokens
        seq = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
        with substrate.use_backend("dequant"), \
                jax.default_matmul_precision("highest"):
            ref = fwd({"base": dep.base, "adapters": dep.adapters},
                      jnp.asarray(seq)[None])
        ref = np.asarray(ref[0, req.prompt_len - 1:], np.float64)
        err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
        assert err < 0.05, f"request {req.rid}: rel. error {err}"
