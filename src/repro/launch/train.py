"""Calibration-training driver.

Runs the paper's DoRA feature-calibration as a production training job:
deterministic data, sharded calib_step under a mesh, periodic async
checkpoints, preemption-safe shutdown, straggler telemetry, and
restart/elastic-resume.

CPU-scale usage (CI / this container):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --smoke --steps 20 --batch 4 --seq 64

On a real pod the same driver runs with --mesh single|multi and the full
config; the step function is identical (it is the one the dry-run lowers).
"""
from __future__ import annotations

import argparse
import hashlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import deploy
from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch
from repro.core.calibrate import CalibState, jit_calib_step, make_calib_step
from repro.data.pipeline import DataConfig, global_batch_at_step
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import setup_compile_cache
from repro.optim.adam import AdamW
from repro.runtime.fault import PreemptionGuard, StepTimer, StragglerDetector
from repro.sharding import rules as sh


def build_state(cfg, seed: int = 0, *, substrate_mode: str = "dequant") -> CalibState:
    """DEPRECATED shim: the deployment (programming event + calib state)
    is owned by ``repro.deploy.Deployment``; use ``dep.calib_state()``."""
    backend = "dequant" if substrate_mode == "dequant" else "codes"
    return deploy.Deployment.program(cfg, seed, backend=backend).calib_state()


def data_config(cfg, *, batch: int, seq: int, samples: int = 10) -> DataConfig:
    return DataConfig(
        vocab=cfg.vocab,
        seq_len=seq,
        global_batch=batch,
        n_calibration_samples=samples,
        enc_src_len=seq if cfg.encoder_layers else 0,
        d_model=cfg.d_model if (cfg.encoder_layers or cfg.vision_tokens) else 0,
        vision_tokens=cfg.vision_tokens,
    )


def train(
    arch_name: str,
    *,
    smoke: bool = False,
    steps: int = 50,
    batch: int = 4,
    seq: int = 64,
    lr: float = 1e-3,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 20,
    use_mesh: Optional[str] = None,  # None | 'single' | 'multi'
    resume: bool = True,
    seed: int = 0,
    log_every: int = 10,
    # Cache teacher features once per distinct calibration batch
    # (Algorithm 1 line 3; §Perf H-9: -29% FLOPs, -17% bytes per step).
    cached_teacher: bool = False,
    # Substrate representation of the programmed student: "dequant"
    # (drifted floats, today's fast path) or "codes" (resident uint8
    # CrossbarWeight leaves). Calibration always EXECUTES codes via the
    # differentiable 'dequant' backend — gradients flow to the adapters
    # while the codes stay frozen; serving can then flip the same
    # deployment to the fused 'codes' backend.
    backend: str = "dequant",
) -> Dict:
    arch = get_arch(arch_name)
    cfg = arch.smoke if smoke else arch.full
    opt = AdamW(lr=lr)
    use_cached = bool(cached_teacher)
    if use_cached:
        from repro.core.calibrate import make_cached_calib_step, teacher_features
        step_fn = make_cached_calib_step(cfg, opt)
    else:
        step_fn = make_calib_step(cfg, opt)
    dcfg = data_config(cfg, batch=batch, seq=seq)

    mesh = None
    if use_mesh:
        mesh = mesh_lib.make_production_mesh(multi_pod=use_mesh == "multi")
        dp, tp = mesh_lib.dp_axes(mesh), mesh_lib.tp_axis(mesh)

    dep = deploy.Deployment.program(cfg, seed, backend=backend)
    state = dep.calib_state()
    print(
        f"deployment: sram_bytes={dep.sram_bytes()} "
        f"({dep.calibrated_fraction():.2%} of params calibrated) "
        f"rram_bytes={dep.rram_bytes()} backend={backend}"
    )
    manager = CheckpointManager(ckpt_dir, keep=3) if ckpt_dir else None
    start_step = 0
    if manager and resume and manager.latest_step() is not None:
        start_step = manager.latest_step()
        restored = manager.restore(
            start_step,
            {"adapters": state.adapters, "opt": state.opt_state},
        )
        state = CalibState(
            state.teacher_base, state.student_base,
            restored["adapters"], restored["opt"],
            jnp.asarray(start_step, jnp.int32),
        )
        dep.adopt(state)
        print(f"resumed from step {start_step}")

    import contextlib

    if mesh is not None:
        ctx = mesh_lib.mesh_context(mesh)
        hint_ctx = sh.logical_axes(dp, tp)
    else:
        ctx = contextlib.nullcontext()
        hint_ctx = contextlib.nullcontext()
    # codes-resident student: execute through the differentiable dequant
    # backend (the fused kernel is inference-shaped; AD needs the jnp path).
    if backend != "dequant":
        from repro import substrate
        backend_ctx = substrate.use_backend("dequant")
    else:
        backend_ctx = contextlib.nullcontext()

    # NOTE: no donation — teacher and student share digital-peripheral
    # buffers (norms/embeddings pass through program_model unchanged), and
    # XLA rejects donating the same buffer twice.
    jit_step = jit_calib_step(step_fn)
    detector = StragglerDetector()
    history = []
    feats_cache = {}
    with ctx, hint_ctx, backend_ctx, PreemptionGuard() as guard:
        for step in range(start_step, steps):
            np_batch = global_batch_at_step(dcfg, step)
            batch_dev = {
                k: jnp.asarray(
                    v, jnp.bfloat16 if v.dtype == np.float32 else None
                )
                for k, v in np_batch.items()
            }
            with StepTimer() as t:
                if use_cached:
                    # distinct calibration batches repeat (10-sample set):
                    # features keyed on batch CONTENT — tokens plus, for
                    # enc-dec/VLM configs, the encoder inputs / vision
                    # prefix — so a repeated batch reuses its trace and a
                    # changed encoder input can never alias a stale one
                    bkey = hashlib.sha1(
                        b"".join(
                            np.ascontiguousarray(np_batch[k]).tobytes()
                            for k in sorted(np_batch)
                        )
                    ).hexdigest()
                    if bkey not in feats_cache:
                        feats_cache[bkey] = teacher_features(
                            state.teacher_base, batch_dev, cfg
                        )
                    state, metrics = jit_step(state, feats_cache[bkey], batch_dev)
                else:
                    state, metrics = jit_step(state, batch_dev)
                loss = float(metrics["loss"])
            detector.record(step, t.elapsed)
            history.append(loss)
            if step % log_every == 0:
                print(f"step {step:5d} loss {loss:.6f} ({t.elapsed*1e3:.0f} ms)")
            if manager and (step + 1) % ckpt_every == 0:
                dep.adopt(state).snapshot(manager, blocking=False)
            if guard.should_stop:
                print("preemption requested: checkpoint + clean exit")
                if manager:
                    dep.adopt(state).snapshot(manager)
                break
    if manager:
        manager.wait()
    dep.adopt(state)
    return {
        "final_loss": history[-1] if history else None,
        "history": history,
        "straggler_reports": detector.reports,
        "state": state,
        "deployment": dep,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--backend", default="dequant", choices=["dequant", "codes"],
        help="substrate representation of the programmed student",
    )
    args = ap.parse_args()
    setup_compile_cache()
    out = train(
        args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, use_mesh=args.mesh, seed=args.seed,
        backend=args.backend,
    )
    print(f"final loss: {out['final_loss']:.6f}")


if __name__ == "__main__":
    main()
