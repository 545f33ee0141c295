#!/usr/bin/env python3
"""Chip smoke test: qwen3-1.7B at its published widths (random weights
from a seed) through program -> drift -> calibrate -> serve on a TPU.

Run from the repository root:

    python chip_smoke.py             # one chip: the whole lifecycle
    python chip_smoke.py --chips 4   # tensor-parallel serving on a (1, 4)
                                     # mesh, compared with one device

Every phase prints one line: wall time, compiles, and the device's memory
(``peak_bytes_in_use``). A failed phase raises and the script exits
non-zero; no phase catches its own failure. The last line of standard
output is ``{"ok": true, "device": {...}}``. With no TPU visible to JAX
(``JAX_PLATFORMS=cpu``), or without the ``src/repro`` package next to
this file, it exits non-zero before programming and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

SEED = 0
DRIFT_HOURS = 24.0
CALIB_SAMPLES = 10           # paper: 10 calibration samples
CALIB_SEQ = 32
CALIB_STEPS = 20
# Drift here is zero-mean noise on every weight, independent from cell to
# cell, and the inputs are uniform random tokens: the part of the error
# rank-8 side-cars can correct on unseen inputs is a few percent, and 10
# samples x 32 tokens are few enough that 20 steps fit those samples
# instead (their logit_mse falls, held-out logit_mse rises). On 320
# samples held-out logit_mse falls (by about 2% at full width on a v5e),
# and this run is gated on it. 640 samples do not fit: the eager teacher
# feature stack runs out of the 16 GB.
LARGE_CALIB_SAMPLES = 320
HELD_OUT_SHAPE = (4, 64)
PROMPT_LENS = (64, 96, 160, 256)   # ragged requests, whole 32-token chunks
MAX_NEW = 16
MAX_LEN = 288                # >= longest prompt + MAX_NEW
PARITY_PROMPT_LEN = 128
# Served logits vs T.forward over the same codes on the dequant backend.
# The two read identical drifted codes and calibrated adapters; they
# differ by rounding only: the dequant reference rounds each weight to
# bf16 before its matmul while the kernel keeps codes x scale in f32,
# and both carry bf16 activations through 28 layers. 0.05 is the bound
# tests/test_backends.py holds this pair of backends to.
PARITY_TOL = 0.05
# The tensor-parallel session shards whole output columns (the full K
# reduction stays on one chip) and reassembles them with an exact
# zero-canvas psum. A narrower shard runs the kernel on another tile plan,
# which sums K in another order; an f32 partial can then round a bf16
# activation the other way, and 28 layers carry that. On four v5e chips
# the mesh session differs from the one-device session by 0.0176..0.0192,
# the same size as the codes-vs-dequant pair above, which also differs by
# rounding only; so it is held to the same bound, and the mesh session is
# also held to the dequant reference like the one-device session.
MESH_TOL = PARITY_TOL


class Phases:
    """Per-phase wall time, backend compiles and device memory."""

    def __init__(self, device):
        import jax

        self.device = device
        self.compiles = 0
        self.compile_secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_secs += duration

    @contextlib.contextmanager
    def phase(self, name):
        c0, s0 = self.compiles, self.compile_secs
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        stats = self.device.memory_stats() or {}
        print(
            f"phase={name} wall_s={wall:.3f} compiles={self.compiles - c0} "
            f"compile_s={self.compile_secs - s0:.3f} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"device_kind={self.device.device_kind!r}",
            flush=True,
        )


def rel_err(a, b) -> float:
    """Relative Frobenius error of ``a`` against the reference ``b``."""
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def served_logits(session, tokens, max_len, feed=None):
    """Logits of the served path: the fused prefill's last position, then
    two decode steps through its cache, fed the greedy tokens (or the
    ``(B, 2)`` tokens of ``feed``). Returns ``(logits (B, 3, V), tokens
    (B, S + 2))`` — the fed tokens extend the prompt."""
    import jax.numpy as jnp

    logits, cache = session.prefill(tokens, max_len)
    outs = [logits[:, -1]]
    toks = [tokens]
    s = tokens.shape[1]
    with session.scope():
        step = session.decode_step()
        for i in range(2):
            if feed is None:
                nxt = jnp.argmax(outs[-1], axis=-1)[:, None]
            else:
                nxt = feed[:, i:i + 1]
            nxt = nxt.astype(jnp.int32)
            toks.append(nxt)
            pos = jnp.full((tokens.shape[0],), s + i, jnp.int32)
            logits, cache = step(session.params, cache, nxt, pos)
            outs.append(logits[:, -1])
    return jnp.stack(outs, axis=1), jnp.concatenate(toks, axis=1)


def reference_logits(dep, tokens):
    """``T.forward`` of the deployment's drifted codes and calibrated
    (unmerged) adapters on the dequant backend at matmul precision
    "highest" — the plain reference the served path is held to."""
    import jax

    from repro import substrate
    from repro.models import transformer as T

    cfg = dep.cfg
    with substrate.use_backend("dequant"), \
            jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: T.forward(p, {"tokens": t}, cfg))
        return fwd({"base": dep.base, "adapters": dep.adapters}, tokens)


def check_mosaic(name, compiled_text):
    """The step must hold the Mosaic kernel: a launch in interpret mode
    compiles to plain XLA ops and carries no ``tpu_custom_call``."""
    n = compiled_text.count("tpu_custom_call")
    if n == 0:
        raise RuntimeError(f"{name}: no tpu_custom_call in the compiled step")
    print(f"mosaic step={name} tpu_custom_call={n}", flush=True)


def check_requests(reqs, vocab):
    """Every request ran to ``MAX_NEW`` in-vocabulary tokens."""
    for r in reqs:
        if not r.done or len(r.tokens) != MAX_NEW:
            raise RuntimeError(
                f"request {r.rid} (prompt {r.prompt_len}) ended with "
                f"{len(r.tokens)} of {MAX_NEW} tokens"
            )
        if not all(0 <= t < vocab for t in r.tokens):
            raise RuntimeError(f"request {r.rid} emitted out-of-vocab tokens")


def run_single(cfg, phases):
    """The whole lifecycle on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.deploy import Deployment, ServeEngine, serving
    from repro.deploy.deployment import calibration_batch
    from repro.models import transformer as T

    key = jax.random.PRNGKey(SEED + 100)
    k_eval, k_prompts, k_parity = jax.random.split(key, 3)

    with phases.phase("program"):
        dep = Deployment.program(cfg, SEED, backend="codes")
        jax.block_until_ready(dep.codes)
    with phases.phase("advance"):
        dep.advance(DRIFT_HOURS)
        jax.block_until_ready(dep.codes)

    cal = calibration_batch(cfg, CALIB_SAMPLES, CALIB_SEQ)
    held = {"tokens": jax.random.randint(k_eval, HELD_OUT_SHAPE, 0, cfg.vocab)}
    with phases.phase("logit_mse_before"):
        mse_before = dep.logit_mse(cal)
        held_before = dep.logit_mse(held)
    print(f"logit_mse before calibration_set={mse_before!r} "
          f"held_out={held_before!r}", flush=True)

    # the paper's calibration: 10 samples; gated on those samples
    with phases.phase("calibrate"):
        report = dep.calibrate(cal, steps=CALIB_STEPS)
    print(f"calibrate losses={report.losses!r}", flush=True)
    with phases.phase("logit_mse_after"):
        mse_after = dep.logit_mse(cal)
        held_after = dep.logit_mse(held)
    print(f"logit_mse after calibration_set={mse_after!r} "
          f"held_out={held_after!r}", flush=True)
    if not mse_after < mse_before:
        raise RuntimeError(
            f"calibration did not lower the calibration set's logit_mse: "
            f"{mse_before} -> {mse_after}"
        )

    # from scratch on a set large enough not to be fitted by heart;
    # gated on held-out tokens, and these side-cars are served
    with phases.phase("calibrate_large"):
        dep.reset_adapters()
        report = dep.calibrate(LARGE_CALIB_SAMPLES, steps=CALIB_STEPS,
                               seq_len=CALIB_SEQ)
        held_large = dep.logit_mse(held)
    print(f"calibrate_large samples={LARGE_CALIB_SAMPLES} "
          f"losses={report.losses!r}", flush=True)
    print(f"logit_mse after calibrate_large held_out={held_large!r} "
          f"(before {held_before!r})", flush=True)
    if not held_large < held_before:
        raise RuntimeError(
            f"calibration on {LARGE_CALIB_SAMPLES} samples did not lower "
            f"held-out logit_mse: {held_before} -> {held_large}"
        )

    with phases.phase("serve"):
        session = dep.serve()
        jax.block_until_ready(session.params)
        engine = ServeEngine(session, max_slots=len(PROMPT_LENS),
                             max_len=MAX_LEN)
        prompts = [
            np.asarray(jax.random.randint(k, (n,), 0, cfg.vocab))
            for k, n in zip(jax.random.split(k_prompts, len(PROMPT_LENS)),
                            PROMPT_LENS)
        ]
        reqs = [engine.submit(p, max_new=MAX_NEW, keep_logits=True)
                for p in prompts]
        engine.run()
    print(f"serve {session.describe()}", flush=True)
    print(f"engine stats={json.dumps(engine.stats())}", flush=True)
    check_requests(reqs, cfg.vocab)

    # the same traffic shape again on a fresh engine: every step is
    # compiled now, so these stats carry no compile time
    with phases.phase("serve_warm"):
        engine = ServeEngine(session, max_slots=len(PROMPT_LENS),
                             max_len=MAX_LEN)
        warm = [engine.submit((p + 1) % cfg.vocab, max_new=MAX_NEW)
                for p in prompts]
        engine.run()
    print(f"engine_warm stats={json.dumps(engine.stats())}", flush=True)
    check_requests(warm, cfg.vocab)

    with phases.phase("engine_parity"):
        # the logits every served token was drawn from (chunked admission,
        # then ragged batched decode) against the reference over prompt +
        # emitted tokens; the forward is causal, so one batch padded at
        # the end serves every request
        seqs = np.zeros((len(reqs), max(PROMPT_LENS) + MAX_NEW - 1), np.int32)
        for i, r in enumerate(reqs):
            seq = np.concatenate([r.prompt, r.tokens[:-1]])
            seqs[i, :seq.size] = seq
        ref = np.asarray(reference_logits(dep, jnp.asarray(seqs)))
        engine_errs = []
        for i, r in enumerate(reqs):
            want = ref[i, r.prompt_len - 1:r.prompt_len - 1 + MAX_NEW]
            engine_errs.append(rel_err(np.stack(r.logits), want))
        del ref
    print(f"engine_parity rel_frobenius per_request={engine_errs!r} "
          f"tol={PARITY_TOL}", flush=True)
    if not all(np.isfinite(engine_errs)) or max(engine_errs) > PARITY_TOL:
        raise RuntimeError(f"served logits off the reference: {engine_errs}")

    with phases.phase("parity"):
        tokens = jax.random.randint(
            k_parity, (len(PROMPT_LENS), PARITY_PROMPT_LEN), 0, cfg.vocab
        )
        got, fed = served_logits(session, tokens, MAX_LEN)
        ref = reference_logits(dep, fed)[:, PARITY_PROMPT_LEN - 1:]
        errs = [rel_err(got[:, i], ref[:, i]) for i in range(got.shape[1])]
    print(f"parity rel_frobenius prefill={errs[0]!r} decode={errs[1:]!r} "
          f"tol={PARITY_TOL}", flush=True)
    if not all(np.isfinite(errs)) or max(errs) > PARITY_TOL:
        raise RuntimeError(f"served logits off the reference: {errs}")

    with phases.phase("mosaic_check"):
        params = session.params
        with session.scope():
            dec = serving.decode_step_fn(cfg).lower(
                params, engine.cache,
                jnp.zeros((len(PROMPT_LENS), 1), jnp.int32),
                jnp.zeros((len(PROMPT_LENS),), jnp.int32),
            ).compile()
            one = T.init_cache(cfg, 1, MAX_LEN)
            chunk = serving.prefill_chunk_fn(cfg).lower(
                params, jnp.zeros((1, engine.prefill_chunk), jnp.int32), one,
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
                MAX_LEN, 0,
            ).compile()
            fused = serving.prefill_fn(cfg).lower(
                params, tokens, MAX_LEN, None, None
            ).compile()
        check_mosaic("decode", dec.as_text())
        check_mosaic("prefill_chunk", chunk.as_text())
        check_mosaic("prefill", fused.as_text())


def run_mesh(cfg, phases, n_model):
    """Tensor-parallel serving on a (1, n_model) ("data", "model") mesh,
    compared with the one-device session of the same deployment."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.deploy import Deployment, serving
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((1, n_model))
    key = jax.random.PRNGKey(SEED + 200)

    with phases.phase("program"):
        dep = Deployment.program(cfg, SEED, backend="codes")
        dep.advance(DRIFT_HOURS)
        jax.block_until_ready(dep.codes)
    with phases.phase("serve_one_device"):
        single = dep.serve()
        jax.block_until_ready(single.params)
    with phases.phase("serve_mesh"):
        sharded = dep.serve(mesh=mesh)
        jax.block_until_ready(sharded.params)
    print(f"mesh shape={dict(mesh.shape)} shard_stats={sharded.shard_stats}",
          flush=True)

    tokens = jax.random.randint(
        key, (len(PROMPT_LENS), PARITY_PROMPT_LEN), 0, cfg.vocab
    )
    with phases.phase("mosaic_check"):
        cache = sharded.prefill(tokens, MAX_LEN)[1]
        with sharded.scope():
            dec = sharded.decode_step().lower(
                sharded.params, cache,
                jnp.zeros((len(PROMPT_LENS), 1), jnp.int32),
                jnp.zeros((len(PROMPT_LENS),), jnp.int32),
            ).compile()
            fused = serving.prefill_fn(cfg, mesh, sharded.params).lower(
                sharded.params, tokens, MAX_LEN, None, None
            ).compile()
        text = dec.as_text()
        check_mosaic("mesh_decode", text)
        check_mosaic("mesh_prefill", fused.as_text())
        print(f"mesh_decode all-reduce={text.count('all-reduce')}", flush=True)

    # bytes of the mesh session's params and of its cache held by each
    # device: sharded leaves split evenly and the rest is replicated, so
    # every device holds the same; a leaf left whole on one device would
    # make that device hold more
    for name, tree in (("params", sharded.params), ("cache", cache)):
        held = {d.id: 0 for d in mesh.devices.flat}
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                held[shard.device.id] += shard.data.nbytes
        print(f"mesh_{name}_bytes per_device={held}", flush=True)
        if len(set(held.values())) != 1:
            raise RuntimeError(f"mesh {name} not spread evenly: {held}")
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        print(f"device id={d.id} bytes_in_use={stats.get('bytes_in_use')} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
              flush=True)

    s = PARITY_PROMPT_LEN
    with phases.phase("one_device_logits"):
        ref, fed = served_logits(single, tokens, MAX_LEN)
        ref = np.asarray(ref)
    with phases.phase("mesh_logits"):
        # the mesh session is fed the one-device session's tokens, so a
        # near-tie argmax cannot make the two decode different streams
        got, _ = served_logits(sharded, tokens, MAX_LEN, feed=fed[:, s:])
        got = np.asarray(got)
    with phases.phase("reference_logits"):
        want = np.asarray(reference_logits(dep, fed)[:, s - 1:])
    errs = [rel_err(got[:, i], ref[:, i]) for i in range(got.shape[1])]
    argmax_equal = bool(np.array_equal(got.argmax(-1), ref.argmax(-1)))
    print(f"mesh_vs_one_device rel_frobenius prefill={errs[0]!r} "
          f"decode={errs[1:]!r} max_abs={float(np.max(np.abs(got - ref)))!r} "
          f"bitwise={bool(np.array_equal(got, ref))} "
          f"argmax_equal={argmax_equal} tol={MESH_TOL}", flush=True)
    ref_errs = {
        name: [rel_err(x[:, i], want[:, i]) for i in range(x.shape[1])]
        for name, x in (("mesh", got), ("one_device", ref))
    }
    print(f"vs_dequant_reference rel_frobenius {ref_errs!r} "
          f"tol={PARITY_TOL}", flush=True)
    if not all(np.isfinite(errs)) or max(errs) > MESH_TOL:
        raise RuntimeError(f"mesh logits off the one-device session: {errs}")
    if not all(np.isfinite(ref_errs["mesh"])) \
            or max(ref_errs["mesh"]) > PARITY_TOL:
        raise RuntimeError(f"mesh logits off the reference: {ref_errs}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="1: the whole lifecycle on one chip; 4: tensor-parallel "
             "serving on a (1, 4) mesh against the one-device session",
    )
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "deploy").is_dir():
        print(f"chip_smoke: the program (src/repro) is not next to this "
              f"script ({src})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"refusing to run on {dev.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import qwen3_1_7b
    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile_cache dir={setup_compile_cache()}", flush=True)
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    phases = Phases(dev)
    t0 = time.perf_counter()
    if args.chips == 1:
        run_single(qwen3_1_7b.FULL, phases)
    else:
        run_mesh(qwen3_1_7b.FULL, phases, args.chips)
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          f"compiles={phases.compiles} compile_s={phases.compile_secs:.3f}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
