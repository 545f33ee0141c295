"""The program's trace spans and program names: the engine tick's
``engine.*`` spans and ``Deployment.calibrate``'s ``calibrate.*`` spans
land on the profiler's host plane, and the serving and calibration
programs lower to modules named after them (``jit_decode_step``,
``jit_prefill_chunk``, ``jit_calib_step``), which is how they show in a
device trace."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import calibrate
from repro.deploy import Deployment, ServeEngine, serving
from repro.deploy.deployment import calibration_batch
from repro.models import transformer as T
from repro.optim.adam import adamw_init

ENGINE_SPANS = ("engine.admit", "engine.slot_write", "engine.decode",
                "engine.sample")
CALIB_SPANS = ("calibrate.prepare", "calibrate.step")


def _host_spans(logdir: Path, names):
    """``[(name, start_ns, end_ns, stats), ...]`` of the host-plane events
    named in ``names``, by start."""
    files = sorted(logdir.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    prof = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    start = int(e.start_ns)
                    out.append((e.name, start, start + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def smoke():
    cfg = get_arch("qwen3-1.7b").smoke
    return cfg, Deployment.program(cfg, 0, backend="dequant")


def test_engine_tick_spans_on_the_host_plane(smoke, tmp_path):
    """A ragged admission (one single-chunk prompt, one of three chunks)
    and a few decode ticks: every tick-phase span is there, each slot
    write lies inside its request's admission unit, and the admission
    units carry the request's id and slot."""
    cfg, dep = smoke
    engine = ServeEngine(dep.serve(), max_slots=2, max_len=48,
                         prefill_chunk=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 19)]
    with jax.profiler.trace(str(tmp_path)):
        reqs = [engine.submit(p, max_new=4) for p in prompts]
        for _ in range(4):
            engine.step()
    assert all(len(r.tokens) >= 2 for r in reqs)
    spans = _host_spans(tmp_path, ENGINE_SPANS)
    assert {s[0] for s in spans} == set(ENGINE_SPANS)
    admits = [s for s in spans if s[0] == "engine.admit"]
    # one unit for the short prompt, three chunks for the long one
    assert sorted(s[3]["rid"] for s in admits) == [
        reqs[0].rid, reqs[1].rid, reqs[1].rid, reqs[1].rid]
    for s in admits:
        rid = s[3]["rid"]
        assert s[3]["slot"] == [0, 1][[r.rid for r in reqs].index(rid)]
    writes = [s for s in spans if s[0] == "engine.slot_write"]
    assert len(writes) == 2
    for w in writes:
        assert any(a[1] <= w[1] and w[2] <= a[2] for a in admits), w
    decodes = [s for s in spans if s[0] == "engine.decode"]
    samples = [s for s in spans if s[0] == "engine.sample"]
    assert len(decodes) == len(samples) == 4
    for d, s in zip(decodes, samples):
        assert d[2] <= s[1]


def test_calibrate_spans(smoke, tmp_path):
    """A two-step ``calibrate`` under the profiler: its prepare span comes
    first, then one span per step."""
    cfg, dep = smoke
    batch = calibration_batch(cfg, 2, 8)
    with jax.profiler.trace(str(tmp_path)):
        report = dep.calibrate(batch, steps=2)
    assert report.epochs_run == 2
    spans = _host_spans(tmp_path, CALIB_SPANS)
    assert [s[0] for s in spans] == [
        "calibrate.prepare", "calibrate.step", "calibrate.step"]
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1]


def test_programs_lower_under_their_names(smoke):
    cfg, dep = smoke
    session = dep.serve()
    slots, max_len = 2, 32
    with session.scope():
        cache = T.init_cache(cfg, slots, max_len)
        text = serving.decode_step_fn(cfg).lower(
            session.params, cache, jnp.zeros((slots, 1), jnp.int32),
            jnp.zeros((slots,), jnp.int32)).as_text()
        assert "module @jit_decode_step" in text
        one = T.init_cache(cfg, 1, max_len)
        text = serving.prefill_chunk_fn(cfg).lower(
            session.params, jnp.zeros((1, 8), jnp.int32), one,
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
            max_len, 0).as_text()
        assert "module @jit_prefill_chunk" in text
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = calibration_batch(cfg, 2, 8)
    feats = calibrate.teacher_features(params["base"], batch, cfg)
    program = calibrate.calib_step_program(
        calibrate.make_cached_calib_step(cfg))
    trainable = (params["adapters"], adamw_init(params["adapters"]),
                 jnp.zeros((), jnp.int32))
    text = jax.jit(program).lower(
        params["base"], params["base"], trainable, feats, batch).as_text()
    assert "module @jit_calib_step" in text


def test_mesh_programs_keep_their_names(smoke):
    """The ``shard_map`` builds of the serving steps, on a one-device
    mesh, lower under the same names."""
    from repro.launch.mesh import make_host_mesh

    cfg = smoke[0]
    dep = Deployment.program(cfg, 0, backend="codes")
    mesh = make_host_mesh((1, 1))
    session = dep.serve(mesh=mesh)
    slots, max_len = 2, 32
    with session.scope():
        cache = T.init_cache(cfg, slots, max_len)
        text = serving.decode_step_fn(cfg, mesh, session.params).lower(
            session.params, cache, jnp.zeros((slots, 1), jnp.int32),
            jnp.zeros((slots,), jnp.int32)).as_text()
        assert "module @jit_decode_step" in text
        one = T.init_cache(cfg, 1, max_len)
        text = serving.prefill_chunk_fn(cfg, mesh, session.params).lower(
            session.params, jnp.zeros((1, 8), jnp.int32), one,
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
            max_len, 0).as_text()
        assert "module @jit_prefill_chunk" in text
