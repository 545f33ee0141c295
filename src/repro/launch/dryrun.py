import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, prove memory fit, and extract roofline
terms. MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun``.

The two lines above run before any jax import so the CPU host platform
exposes 512 placeholder devices; nothing here allocates device memory —
all inputs/params are ShapeDtypeStructs.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import deploy  # noqa: E402
from repro.configs import ARCH_IDS, get_arch, input_specs  # noqa: E402
from repro.configs.shapes import ArchSpec, ShapeSpec  # noqa: E402
from repro.core.calibrate import calib_step_program, make_calib_step  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch.roofline import Roofline, collective_bytes  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim.adam import AdamW  # noqa: E402
from repro.sharding import rules as sh  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts")

# Abstract deployment views (eval_shape) come from the lifecycle API so
# the compile planner and the live drivers build the same structures.
abstract_params = deploy.abstract_params


def _model_flops(cfg, arch, params_abs, shape: ShapeSpec, n_devices: int) -> float:
    """Useful-model-FLOPs per device: 2*N_active per token forward,
    6*N_active per token for the calibration step (teacher fwd + student
    fwd + adapter backward ~ 2N each)."""
    base = params_abs["base"]
    n_total = sum(x.size for x in jax.tree_util.tree_leaves(base))
    embed = base["embed"]["embedding"].size
    n_mat = n_total - embed
    if cfg.moe is not None:
        frac = T.active_param_fraction(cfg, params_abs)
        n_mat = n_mat * frac
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        per_tok = 6.0 * n_mat
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        per_tok = 2.0 * n_mat
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        per_tok = 2.0 * n_mat
    return per_tok * tokens / n_devices


def build_step(arch: ArchSpec, shape: ShapeSpec, mesh, *, smoke=False,
               cfg_override=None):
    """Returns (fn, args_abstract, in_shardings, out_shardings, params)."""
    cfg = cfg_override if cfg_override is not None else (
        arch.smoke if smoke else arch.full
    )
    dp = mesh_lib.dp_axes(mesh)
    tp = mesh_lib.tp_axis(mesh)
    params_abs = abstract_params(cfg)
    p_sh = sh.param_shardings(params_abs, mesh, dp=dp, tp=tp)
    batch_abs = input_specs(arch, shape, smoke=smoke)
    b_sh = sh.batch_shardings(batch_abs, mesh, dp=dp, tp=tp)

    if shape.kind == "train":
        opt = AdamW(lr=1e-3)
        program = calib_step_program(make_calib_step(cfg, opt))
        state_abs = deploy.abstract_calib_state(cfg, params_abs)
        opt_abs = state_abs.opt_state
        opt_sh = sh.tree_shardings(opt_abs, mesh, (), dp=dp, tp=tp)
        step_sh = sh.tree_shardings(
            jax.ShapeDtypeStruct((), jnp.int32), mesh, (), dp=dp, tp=tp
        )
        trainable_abs = (state_abs.adapters, opt_abs, state_abs.step)
        trainable_sh = (p_sh["adapters"], opt_sh, step_sh)
        return (
            program,
            (state_abs.teacher_base, state_abs.student_base, trainable_abs,
             batch_abs),
            (p_sh["base"], p_sh["base"], trainable_sh, b_sh),
            (trainable_sh, None),
            params_abs,
        )

    # inference paths serve the MERGED adapters (Algorithm 2 line 12)
    merged_abs = deploy.abstract_serve_params(cfg, params_abs)["adapters"]
    m_sh = sh.tree_shardings(merged_abs, mesh, (), dp=dp, tp=tp)
    p_sh_serve = {"base": p_sh["base"], "adapters": m_sh}

    if shape.kind == "prefill":
        def prefill(params, batch):
            return T.forward(params, batch, cfg)
        return (
            prefill,
            ({"base": params_abs["base"], "adapters": merged_abs}, batch_abs),
            (p_sh_serve, b_sh),
            None,
            params_abs,
        )

    # decode
    max_len = shape.seq_len
    src = min(arch.enc_src_len or 4096, 4096)
    cache_abs = jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, max_len, src_len=src)
    )
    c_sh = sh.cache_shardings(cache_abs, mesh, dp=dp, tp=tp)

    def decode(params, cache, tokens, pos):
        return T.decode_step(params, cache, tokens, pos, cfg)

    args_abs = (
        {"base": params_abs["base"], "adapters": merged_abs},
        cache_abs,
        batch_abs["tokens"],
        batch_abs["pos"],
    )
    tok_sh = sh.batch_shardings(batch_abs, mesh, dp=dp, tp=tp)
    in_sh = (p_sh_serve, c_sh, tok_sh["tokens"], tok_sh["pos"])
    out_sh = (None, c_sh)
    return decode, args_abs, in_sh, out_sh, params_abs


def _compile_once(arch, cfg, shape, mesh, *, smoke=False):
    """Lower + compile one variant; returns (compiled, params_abs)."""
    dp = mesh_lib.dp_axes(mesh)
    tp = mesh_lib.tp_axis(mesh)
    with mesh_lib.mesh_context(mesh), sh.logical_axes(dp, tp):
        fn, args_abs, in_sh, out_sh, params_abs = build_step(
            arch, shape, mesh, smoke=smoke, cfg_override=cfg
        )
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args_abs)
        compiled = lowered.compile()
    return compiled, params_abs


def _extract(compiled) -> Dict:
    cost = compiled.cost_analysis()
    try:
        mem = compiled.memory_analysis()
        peak = getattr(mem, "peak_memory_in_bytes", None)
        if not peak:
            peak = (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            )
    except Exception:
        peak = None
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(v for k, v in coll.items() if k != "_counts")),
        "coll_breakdown": coll,
        "peak": peak,
        "hlo": hlo,
    }


def _depth_units(cfg) -> Tuple[int, int, int]:
    """(prologue, period, full_n_periods[, epilogue folded into reduce])."""
    p = cfg.scan_period
    pro = cfg.prologue_layers
    body = cfg.n_layers - pro
    n_full = body // p
    epi = body % p
    return pro, p, n_full, epi


def _reduced_cfg(cfg, n_periods: int):
    """Depth-reduced unrolled variant with identical per-period structure
    (prologue + n_periods*period + the full config's epilogue remainder)."""
    import dataclasses as _dc
    pro, p, _, epi = _depth_units(cfg)
    n_layers = pro + n_periods * p + epi
    enc = cfg.encoder_layers
    if enc:
        # scale the encoder with the decoder so the extrapolation unit is
        # "one enc layer + one dec layer"
        enc = max(1, round(enc * n_layers / cfg.n_layers))
    return _dc.replace(cfg, n_layers=n_layers, encoder_layers=enc, unroll=True)


def run_cell(
    arch_id: str, shape_name: str, *, multi_pod: bool, smoke: bool = False,
    keep_hlo: bool = False, roofline: bool = True,
) -> Tuple[Optional[Roofline], Optional[str]]:
    """One (arch, shape, mesh) cell.

    1. FULL config, scan-grouped layers: lower + compile — this is
       deliverable (e): proves sharding coherence + memory fit (peak
       memory from the real full-size artifact).
    2. (single-pod only, roofline=True) two depth-REDUCED unrolled
       variants: per-layer costs are affine in depth, so the full-depth
       FLOPs/bytes/collective-bytes are the exact affine extrapolation
       (lax.scan bodies are otherwise counted once by cost_analysis, not
       once per trip — see EXPERIMENTS.md §Roofline, Method).
    """
    arch = get_arch(arch_id)
    if shape_name in arch.skips:
        return None, f"SKIP {arch_id} {shape_name}: {arch.skips[shape_name]}"
    shape = arch.shapes[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = arch.smoke if smoke else arch.full

    t0 = time.time()
    compiled_full, params_abs = _compile_once(arch, cfg, shape, mesh, smoke=smoke)
    full_stats = _extract(compiled_full)
    t1 = time.time()
    msg = (
        f"OK   {arch_id} {shape_name} mesh={mesh_name} compile={t1-t0:.1f}s "
        f"peak_mem={(full_stats['peak'] or 0)/2**30:.2f}GiB"
    )
    if keep_hlo:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        with open(
            os.path.join(ARTIFACT_DIR, f"{arch_id}_{shape_name}_{mesh_name}.hlo"),
            "w",
        ) as f:
            f.write(full_stats["hlo"])

    if multi_pod or not roofline:
        # multi-pod cells prove the "pod" axis shards; the roofline table
        # is single-pod only (assignment spec).
        return None, msg

    pro, p, n_full, epi = _depth_units(cfg)
    n1, n2 = 1, 2
    del compiled_full
    c1, _ = _compile_once(arch, _reduced_cfg(cfg, n1), shape, mesh, smoke=smoke)
    s1 = _extract(c1)
    del c1
    c2, _ = _compile_once(arch, _reduced_cfg(cfg, n2), shape, mesh, smoke=smoke)
    s2 = _extract(c2)
    del c2

    def extrap(k):
        slope = (s2[k] - s1[k]) / (n2 - n1)
        return s1[k] + slope * (n_full - n1)

    coll_bd = {
        kind: (
            s1["coll_breakdown"][kind]
            + (s2["coll_breakdown"][kind] - s1["coll_breakdown"][kind])
            * (n_full - n1) / (n2 - n1)
        )
        for kind in s1["coll_breakdown"]
        if kind != "_counts"
    }
    rl = Roofline(
        arch=arch_id,
        shape=shape_name,
        mesh=mesh_name,
        flops=extrap("flops"),
        bytes_accessed=extrap("bytes"),
        coll_bytes=extrap("coll"),
        coll_breakdown=coll_bd,
        peak_memory=full_stats["peak"],
        model_flops=_model_flops(cfg, arch, params_abs, shape, mesh.size),
    )
    return rl, msg + " | " + rl.summary()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--smoke", action="store_true", help="use smoke configs")
    ap.add_argument("--out", default=None, help="write roofline JSON here")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    rows, failures = [], []
    for arch_id in archs:
        arch = get_arch(arch_id)
        shape_names = (
            list(arch.shapes) + list(arch.skips)
            if args.shape == "all"
            else [args.shape]
        )
        for shape_name in shape_names:
            for multi_pod in meshes:
                try:
                    rl, msg = run_cell(
                        arch_id, shape_name, multi_pod=multi_pod,
                        smoke=args.smoke, keep_hlo=args.keep_hlo,
                    )
                    print(msg, flush=True)
                    if rl is not None:
                        rows.append(rl)
                except Exception as e:  # a failure here is a bug in our system
                    failures.append((arch_id, shape_name, multi_pod, repr(e)))
                    print(
                        f"FAIL {arch_id} {shape_name} multi_pod={multi_pod}: {e}",
                        flush=True,
                    )
                    traceback.print_exc()
                if shape_name in arch.skips:
                    break  # skip message printed once, not per mesh
    if args.out:
        from repro.launch.roofline import save_rooflines
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        save_rooflines(rows, args.out)
        print(f"wrote {len(rows)} rooflines to {args.out}")
    print(f"\n{len(rows)} cells OK, {len(failures)} failures")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
