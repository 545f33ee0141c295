"""Deployment lifecycle API: legacy-shim parity, drift clock,
snapshot/restore, and the multi-drift-epoch scenario the one-shot API
could not represent (ISSUE 3 acceptance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import deploy, substrate
from repro.configs import get_arch
from repro.core import calibrate as C
from repro.core import rram
from repro.deploy import Deployment
from repro.launch import serve, train
from repro.optim.adam import AdamW


def _cfg():
    return get_arch("qwen3_1_7b").smoke


def _batch(cfg, b=2, s=16, seed=0):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab
    )}


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(
        a, is_leaf=lambda n: isinstance(n, rram.CrossbarWeight)
    )
    lb = jax.tree_util.tree_leaves(
        b, is_leaf=lambda n: isinstance(n, rram.CrossbarWeight)
    )
    assert len(la) == len(lb) and len(la) > 0
    for x, y in zip(la, lb):
        if isinstance(x, rram.CrossbarWeight):
            assert isinstance(y, rram.CrossbarWeight)
            np.testing.assert_array_equal(np.asarray(x.g_pos), np.asarray(y.g_pos))
            np.testing.assert_array_equal(np.asarray(x.g_neg), np.asarray(y.g_neg))
            np.testing.assert_array_equal(np.asarray(x.scale), np.asarray(y.scale))
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- legacy shim parity ------------------------------------------------------


@pytest.mark.parametrize("backend,mode", [("dequant", "dequant"), ("codes", "codes")])
def test_deployment_parity_with_legacy_free_functions(backend, mode):
    """program_model + merge_adapters_for_serve + backend scoping (the
    legacy wiring) vs Deployment: bitwise-identical resident base and
    identical logits for the same seed/arch/backend."""
    cfg = _cfg()
    seed = 0
    # legacy wiring, exactly as launch/serve.py used to hand-build it
    from repro.models import transformer as T
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    legacy_base = C.program_model(
        params["base"], cfg.rram, jax.random.PRNGKey(seed + 1), mode=mode
    )
    legacy = {
        "base": legacy_base,
        "adapters": C.merge_adapters_for_serve(legacy_base, params["adapters"]),
    }
    dep = Deployment.program(cfg, seed, backend=backend)
    session = dep.serve()
    # the deployment's resident base and merged adapters are bitwise the
    # legacy wiring's; under codes the SESSION additionally carries the
    # prepared (padded/fused) serving tree, so compare the source trees
    _assert_trees_equal(legacy["base"], dep.base)
    _assert_trees_equal(legacy["adapters"], session.params["adapters"])

    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab)
    with deploy.backend_scope(backend, cfg):
        logits_legacy, _ = deploy.prefill_and_cache(legacy, prompt, cfg, 8)
    logits_dep, _ = session.prefill(prompt, 8)
    np.testing.assert_array_equal(
        np.asarray(logits_legacy), np.asarray(logits_dep)
    )


def test_load_student_shim_matches_deployment_serve():
    cfg = _cfg()
    shim = serve.load_student(cfg, seed=3, backend="codes")
    dep = Deployment.program(cfg, 3, backend="codes")
    session = dep.serve()
    # the shim keeps the legacy raw layout; the session's serving tree is
    # prepared (padded/fused) but derives from the same base + adapters
    _assert_trees_equal(shim["base"], dep.base)
    _assert_trees_equal(shim["adapters"], session.params["adapters"])
    # and both serve identical logits for the same prompt
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 4), 0, cfg.vocab)
    with deploy.backend_scope("codes", cfg):
        logits_shim, _ = deploy.prefill_and_cache(shim, prompt, cfg, 6)
    logits_dep, _ = session.prefill(prompt, 6)
    np.testing.assert_array_equal(
        np.asarray(logits_shim), np.asarray(logits_dep)
    )


def test_build_state_shim_matches_deployment_calib_state():
    cfg = _cfg()
    state = train.build_state(cfg, seed=1)
    dep_state = Deployment.program(cfg, 1).calib_state()
    _assert_trees_equal(state.student_base, dep_state.student_base)
    _assert_trees_equal(state.adapters, dep_state.adapters)


# -- drift clock -------------------------------------------------------------


def test_advance_deterministic_per_event_index():
    cfg = _cfg()
    d1 = Deployment.program(cfg, 0, backend="codes").advance(24.0)
    d2 = Deployment.program(cfg, 0, backend="codes").advance(24.0)
    _assert_trees_equal(d1.codes, d2.codes)
    # a second tick of the SAME duration draws fresh noise (event index
    # is folded into the key) and compounds on the first
    before = jax.tree_util.tree_map(
        lambda x: np.asarray(x),
        d1.codes["body"][0]["mixer"]["q"]["w"].g_pos,
    )
    d1.advance(24.0)
    after = np.asarray(d1.codes["body"][0]["mixer"]["q"]["w"].g_pos)
    assert not np.array_equal(before, after)
    assert d1.drift_hours == [24.0, 24.0]


def test_advance_degrades_agreement_monotonically():
    cfg = _cfg()
    dep = Deployment.program(cfg, 0)
    batch = _batch(cfg)
    gap0 = dep.logit_mse(batch, use_adapters=False)
    dep.advance(24.0)
    gap1 = dep.logit_mse(batch, use_adapters=False)
    dep.advance(168.0)
    gap2 = dep.logit_mse(batch, use_adapters=False)
    assert gap0 < gap1 < gap2


def test_advance_zero_hours_is_noop_and_negative_raises():
    """hours=0 is a TRUE no-op — no drift event is recorded (it used to
    append a zero-hour event that consumed an event index, shifting the
    keys of every later tick); negative hours are rejected instead of
    passing silently through the drift model."""
    cfg = _cfg()
    dep = Deployment.program(cfg, 0, backend="codes")
    ref = jax.tree_util.tree_map(
        lambda x: x, dep.codes,
        is_leaf=lambda n: isinstance(n, rram.CrossbarWeight),
    )
    dep.advance(0.0)
    _assert_trees_equal(ref, dep.codes)
    assert dep.drift_hours == []  # no event recorded
    with pytest.raises(ValueError):
        dep.advance(-1.0)
    assert dep.drift_hours == []
    # a zero tick between real ticks does not perturb the event stream:
    # [24] and [0, 24, 0] replay to the same codes
    d1 = Deployment.program(cfg, 0, backend="codes").advance(24.0)
    d2 = Deployment.program(cfg, 0, backend="codes")
    d2.advance(0.0); d2.advance(24.0); d2.advance(0.0)
    _assert_trees_equal(d1.codes, d2.codes)
    assert d1.drift_hours == d2.drift_hours == [24.0]


def test_drift_sigma_log_time():
    cfg = rram.RramConfig(relative_drift=0.1)
    assert rram.drift_sigma(cfg, 0.0) == 0.0
    s24 = rram.drift_sigma(cfg, 24.0)
    s168 = rram.drift_sigma(cfg, 168.0)
    assert 0 < s24 < s168 < 0.1 * np.log1p(168 / 24.0) + 1e-9
    with pytest.raises(ValueError):
        rram.drift_sigma(cfg, -1.0)


def test_drift_sigma_increments_compose():
    """Slicing the same field time into ticks accumulates the same total
    drift variance: sum of increment variances == total variance, so one
    advance(24) and 24x advance(1) model the same 24 field-hours."""
    cfg = rram.RramConfig(relative_drift=0.1)
    total = rram.drift_sigma(cfg, 24.0)
    acc, t = 0.0, 0.0
    for _ in range(24):
        inc = rram.drift_sigma_increment(cfg, t, 1.0)
        acc += inc * inc
        t += 1.0
    assert np.isclose(np.sqrt(acc), total)
    assert np.isclose(rram.drift_sigma_increment(cfg, 0.0, 24.0), total)
    assert rram.drift_sigma_increment(cfg, 24.0, 0.0) == 0.0


def test_drift_model_rejects_float_trees():
    cfg = _cfg()
    dep = Deployment.program(cfg, 0)  # dequant backend: base is floats
    with pytest.raises(ValueError):
        C.drift_model(
            dep.base, cfg.rram, dep.program_key, hours=1.0, event_index=0
        )


# -- snapshot / restore ------------------------------------------------------


def test_snapshot_restore_reproduces_post_drift_post_calib_state(tmp_path):
    cfg = _cfg()
    dep = Deployment.program(cfg, 0, backend="codes")
    dep.advance(24.0)
    batch = _batch(cfg, b=2, s=16)
    dep.calibrate(batch, steps=4, lr=2e-3)
    dep.advance(12.0)
    step = dep.snapshot(str(tmp_path))

    restored = Deployment.restore(cfg, str(tmp_path))
    assert restored.backend == "codes"
    assert restored.step == step
    assert restored.drift_hours == dep.drift_hours
    _assert_trees_equal(dep.codes, restored.codes)
    _assert_trees_equal(dep.adapters, restored.adapters)
    _assert_trees_equal(dep.opt_state, restored.opt_state)
    # the served artifact is identical
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 0, cfg.vocab)
    l1, _ = dep.serve().prefill(prompt, 6)
    l2, _ = restored.serve().prefill(prompt, 6)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_restore_replays_legacy_zero_hour_events(tmp_path):
    """Snapshots written before advance(0) became a no-op can contain
    recorded zero-hour events that consumed an event index; restore must
    replay that index consumption (not skip it) so later ticks draw the
    same per-event keys."""
    cfg = _cfg()
    dep = Deployment.program(cfg, 0, backend="codes")
    # simulate the legacy state: a zero-hour event on the record, codes
    # untouched (exactly what the old advance(0.0) did), then a real tick
    # drawing under event_index 1
    dep.drift_hours.append(0.0)
    dep.advance(24.0)
    dep.snapshot(str(tmp_path))
    restored = Deployment.restore(cfg, str(tmp_path))
    assert restored.drift_hours == [0.0, 24.0]
    _assert_trees_equal(dep.codes, restored.codes)


def test_restore_backend_override(tmp_path):
    cfg = _cfg()
    dep = Deployment.program(cfg, 0, backend="dequant")
    dep.advance(24.0)
    dep.snapshot(str(tmp_path))
    restored = Deployment.restore(cfg, str(tmp_path), backend="codes")
    assert restored.backend == "codes"
    # same programming event either way: the codes match bitwise
    _assert_trees_equal(dep.codes, restored.codes)


# -- the multi-drift-epoch scenario (acceptance) -----------------------------


def test_two_drift_epoch_lifecycle():
    """program -> advance -> calibrate -> advance -> recalibrate -> serve:
    feature MSE is restored after EACH calibration, which the one-shot
    free-function API structurally could not express."""
    cfg = _cfg()
    dep = Deployment.program(cfg, 0)
    batch = _batch(cfg, b=4, s=16)

    dep.advance(24.0)
    r1 = dep.calibrate(batch, steps=12, lr=3e-3)
    assert r1.final_loss < r1.initial_loss  # calibration restored accuracy
    assert r1.drift_events == 1

    dep.advance(168.0)
    r2 = dep.calibrate(batch, steps=12, lr=3e-3)
    assert r2.initial_loss > r1.final_loss  # drift degraded it again
    assert r2.final_loss < r2.initial_loss  # ...and was restored again
    assert r2.drift_events == 2

    session = dep.serve()
    toks, _ = session.generate(batch["tokens"][:2, :4], gen_len=3)
    assert toks.shape == (2, 3)
    # report carries the SRAM/fraction accounting
    assert r2.sram_bytes == dep.sram_bytes() > 0
    assert 0 < r2.calibrated_fraction < 1


def test_calibration_report_fields():
    cfg = _cfg()
    dep = Deployment.program(cfg, 0)
    report = dep.calibrate(2, steps=3, seq_len=8)
    assert report.epochs_run == len(report.losses) == 3
    assert report.sram_bytes == C.sram_bytes(dep.adapters)
    assert report.rram_bytes == C.rram_bytes(dep.base)
    assert report.adapter_params > 0 and report.base_params > 0
    assert report.backend == "dequant"
    assert "sram_bytes" in report.summary()


# -- compiled programs reused across calibrate calls ------------------------
#
# Each test takes an lr no other test uses, so the registry entries it
# reads are its own.


def _calib_entry(kind, cfg, opt, backend=("dequant", ())):
    return C._CALIB_PROGRAMS[(kind, cfg, opt, backend)]


def test_calibrate_builds_its_programs_once():
    """Repeated same-shape calls compile nothing new; a new batch shape
    adds exactly one compile to the step and one to the teacher forward,
    on the same entries."""
    cfg = _cfg()
    opt = AdamW(lr=8e-4)
    dep = Deployment.program(cfg, 0, backend="codes").advance(24.0)
    batch = _batch(cfg, b=2, s=16)
    dep.calibrate(batch, steps=2, opt=opt)
    first = C.calib_compile_count(cfg)
    step = _calib_entry("cached", cfg, opt)
    teacher = _calib_entry("teacher", cfg, None)
    assert (step._cache_size(), teacher._cache_size()) == (1, 1)

    # an equal AdamW made anew hits the same entry
    dep.calibrate(batch, steps=2, opt=AdamW(lr=8e-4))
    dep.calibrate(batch, steps=3, opt=opt)
    assert C.calib_compile_count(cfg) == first

    dep.calibrate(_batch(cfg, b=3, s=16), steps=2, opt=opt)
    assert _calib_entry("cached", cfg, opt) is step
    assert (step._cache_size(), teacher._cache_size()) == (2, 2)
    assert C.calib_compile_count(cfg) == first + 2


@pytest.mark.parametrize("variant", ["opt", "fused"])
def test_calibrate_registry_separates_opt_and_kind(variant):
    """A different optimizer, or the fused (uncached-teacher) step, is a
    different program and builds its own entry."""
    cfg = _cfg()
    opt = AdamW(lr=9e-4)
    dep = Deployment.program(cfg, 0)
    batch = _batch(cfg, b=2, s=16)
    dep.calibrate(batch, steps=1, opt=opt)
    before = set(C._CALIB_PROGRAMS)
    if variant == "opt":
        other = AdamW(lr=9e-4, weight_decay=0.01)
        dep.calibrate(batch, steps=1, opt=other)
        expected = ("cached", cfg, other)
    else:
        dep.calibrate(batch, steps=1, opt=opt, cached_teacher=False)
        expected = ("fused", cfg, opt)
    added = set(C._CALIB_PROGRAMS) - before
    assert [k[:3] for k in added] == [expected]
    assert C._CALIB_PROGRAMS[added.pop()]._cache_size() == 1


def test_registry_calibrate_matches_per_call_jit():
    """Two successive calls through the registry give, bitwise, the
    losses, adapters and optimizer state of the per-call path: a fresh
    jit of the step and eager teacher features in every call."""
    cfg = _cfg()
    opt = AdamW(lr=1.5e-3)
    batch = _batch(cfg, b=2, s=16, seed=3)
    dep = Deployment.program(cfg, 0, backend="codes").advance(24.0)
    losses = [dep.calibrate(batch, steps=3, opt=opt).losses
              for _ in range(2)]

    ref = Deployment.program(cfg, 0, backend="codes").advance(24.0)
    ref_losses = []
    with substrate.use_backend("dequant"):
        for _ in range(2):
            state = ref.calib_state()
            feats = C.teacher_features(ref.teacher_base, batch, cfg)
            step_fn = C.jit_calib_step(C.make_cached_calib_step(cfg, opt))
            call = []
            for _ in range(3):
                state, metrics = step_fn(state, feats, batch)
                call.append(float(metrics["loss"]))
            ref.adopt(state)
            ref_losses.append(call)

    assert losses == ref_losses
    _assert_trees_equal(dep.adapters, ref.adapters)
    _assert_trees_equal(dep.opt_state, ref.opt_state)
    assert dep.step == ref.step == 6


def test_calibrate_registry_keys_on_backend_options():
    """A dequant deployment calibrated under an ambient backend scope
    with options, then without it, uses two registry entries: a step
    traced under one backend is never reused under another."""
    cfg = _cfg()
    opt = AdamW(lr=6e-4)
    batch = _batch(cfg, b=2, s=16)
    dep = Deployment.program(cfg, 0)

    def backends():
        return {k[3] for k in C._CALIB_PROGRAMS
                if k[:3] == ("cached", cfg, opt)}

    with substrate.use_backend("codes", accum="int8"):
        scoped = substrate.active_backend_key()
        r1 = dep.calibrate(batch, steps=2, opt=opt)
    assert backends() == {scoped}
    dep.reset_adapters()
    r2 = dep.calibrate(batch, steps=2, opt=opt)
    ambient = substrate.active_backend_key()
    assert scoped != ambient
    assert backends() == {scoped, ambient}
    for b in (scoped, ambient):
        assert _calib_entry("cached", cfg, opt, b)._cache_size() == 1
    # a float base never reaches the backend: both entries agree
    assert r1.losses == r2.losses


# -- serving fixes -----------------------------------------------------------


def test_generate_samples_first_token():
    """temperature > 0 must sample EVERY generated token, including the
    first (it used to be argmax'd regardless)."""
    cfg = _cfg()
    session = Deployment.program(cfg, 0).serve()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab)
    t1, _ = session.generate(
        prompt, gen_len=1, temperature=8.0, key=jax.random.PRNGKey(10)
    )
    t2, _ = session.generate(
        prompt, gen_len=1, temperature=8.0, key=jax.random.PRNGKey(11)
    )
    # near-uniform sampling over the vocab: different keys give a
    # different first token (argmax would be identical every time)
    assert not np.array_equal(t1, t2)
    # greedy path stays deterministic
    g1, _ = session.generate(prompt, gen_len=1, temperature=0.0)
    g2, _ = session.generate(prompt, gen_len=1, temperature=0.0)
    np.testing.assert_array_equal(g1, g2)


def test_sram_bytes_measures_adapter_arrays():
    cfg = _cfg()
    dep = Deployment.program(cfg, 0)
    expected = sum(
        int(x.nbytes) for x in jax.tree_util.tree_leaves(dep.adapters)
    )
    assert C.sram_bytes(dep.adapters) == expected > 0
    assert 0 < C.calibrated_fraction(dep.base, dep.adapters) < 1
