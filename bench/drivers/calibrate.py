"""Calibration: ``Deployment.calibrate`` on a drifted deployment, called
back to back on one batch of token rows drawn from the seed.

Set-up makes the teacher, the drifted codes and the paper's initial
side-cars, builds the ``Deployment``, and drives it through its first
three steps with the window's own call (one step, then two), which also
compiles everything the window runs. The window then makes whole
``calibrate`` calls of ``steps_per_call`` steps until ``--seconds`` have
passed; the time per step is the window over every step of those calls.

``correct`` compares those first three steps with the plain reference
(``bench/lib/reference.calibrate``): each step's loss, the gradient the
optimizer applied in step 1 (read back from its first moment), and the
change of every side-car after step 3, each by the worst leaf.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import flops, reference, stats, weights
from bench.lib import program as prog


def _from_program(ads):
    body = ads["body"][0]
    out = {"layers": {**body["mixer"], **body["ffn"]}}
    if "lm_head" in ads:
        out["lm_head"] = ads["lm_head"]
    return out


def leaf_norms(tree) -> dict:
    """Float64 L2 norm of every leaf, keyed by its path."""
    import jax

    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel()))
        for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def diff_norms(new, old) -> dict:
    import jax

    return leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype("float32") - y.astype("float32"), new, old))


def gap(prog_norms: dict, ref_norms: dict, keep=None) -> float:
    """Worst leaf of |program norm - reference norm| over the larger of the
    reference leaf's norm and the median leaf's."""
    keys = [k for k in ref_norms if keep is None or k in keep]
    med = stats.median([ref_norms[k] for k in keys])
    return max(abs(prog_norms[k] - ref_norms[k]) / max(ref_norms[k], med)
               for k in keys)


def opt_dict(opt) -> dict:
    return {"lr": opt.lr, "b1": opt.b1, "b2": opt.b2, "eps": opt.eps,
            "weight_decay": opt.weight_decay, "grad_clip": opt.grad_clip}


def setup(env, spec, mix, seed):
    import jax
    import jax.numpy as jnp

    from repro.deploy import Deployment
    from repro.optim.adam import AdamW

    cfg = prog.model_config(spec)
    w, a, t = weights.make(spec, seed, teacher=True, adapters="init")
    jax.block_until_ready((w, a, t))
    codes, ads, t_base = prog.to_program(cfg, w, a, t)
    key = weights.seed_key(seed)
    dep = Deployment(cfg, "codes", t_base, codes, ads, key,
                     jax.random.fold_in(key, 1))
    toks = weights.tokens(seed, 7, (mix["samples"], mix["seq_len"]),
                          spec["vocab_size"])
    batch = {"tokens": jnp.asarray(toks)}
    opt = AdamW(lr=mix["lr"])
    r1 = dep.calibrate(batch, steps=1, opt=opt)
    grad1 = jax.tree_util.tree_map(lambda m: m / (1.0 - opt.b1),
                                   dep.opt_state.mu)
    first = {"grad": leaf_norms(_from_program(grad1))}
    r2 = dep.calibrate(batch, steps=2, opt=opt)
    first["losses"] = list(r1.losses) + list(r2.losses)
    first["change"] = diff_norms(_from_program(dep.adapters), a)
    return {"cfg": cfg, "w": w, "a": a, "t": t, "dep": dep, "batch": batch,
            "opt": opt, "tokens": toks, "first": first}


def run_window(env, state, mix, seconds):
    dep, batch, opt = state["dep"], state["batch"], state["opt"]
    compiles0, loads0 = env.compiles, env.cache_loads
    calls = 0
    t0 = time.perf_counter()
    while True:
        with env.span("calibrate"):
            dep.calibrate(batch, steps=mix["steps_per_call"], opt=opt)
        calls += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window = time.perf_counter() - t0
    if env.tracing:
        # the profiler records one more call, after the timed window
        env.trace_start()
        with env.span("calibrate"):
            dep.calibrate(batch, steps=mix["steps_per_call"], opt=opt)
        env.trace_stop()
    return {"window_s": window, "calls": calls,
            "steps": calls * mix["steps_per_call"],
            "compiles": env.compiles - compiles0,
            "cache_loads": env.cache_loads - loads0}


def check(spec, state, lowp=None, fault=None):
    """The three first steps against the reference. With ``lowp`` the
    reference in that precision stands in the program's place; with
    ``fault="half_batch"`` the reference over the first half of the rows
    does."""
    first = state["first"]
    opt = opt_dict(state["opt"])
    losses, grads, params = reference.calibrate(
        spec, state["w"], state["a"], state["t"], state["tokens"], opt, 3)
    ref_grad = leaf_norms(grads[0])
    ref_change = diff_norms(params, state["a"])
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: leave them out of the change
    moved = {}
    for g in grads:
        for k, v in leaf_norms(g).items():
            moved[k] = max(moved.get(k, 0.0), v)
    med = stats.median(list(moved.values()))
    keep = {k for k, v in moved.items() if v >= 1e-3 * med}
    if lowp is not None or fault is not None:
        rows = state["tokens"]
        if fault == "half_batch":
            rows = rows[:len(rows) // 2]
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        l2, g2, p2 = reference.calibrate(
            spec, state["w"], state["a"], state["t"], rows, opt, 3,
            lowp=lowp)
        first = {"losses": l2, "grad": leaf_norms(g2[0]),
                 "change": diff_norms(p2, state["a"])}
    return {
        "loss_gap": max(abs(p - r) / r
                        for p, r in zip(first["losses"], losses)),
        "grad_gap": gap(first["grad"], ref_grad),
        "change_gap": gap(first["change"], ref_change, keep),
        "leaves_left_out": sorted(set(ref_change) - keep),
        "program_losses": first["losses"], "reference_losses": losses,
    }


def run(env, spec, mix, seed, seconds):
    import jax

    with env.phase("setup"):
        state = setup(env, spec, mix, seed)
    counters = run_window(env, state, mix, seconds)
    env.memory_peak()
    del state["dep"]
    jax.clear_caches()
    readings = check(spec, state)
    W = counters["window_s"]
    env.log(f"window_s={W!r} calls={counters['calls']} "
            f"steps={counters['steps']} "
            f"compiles_in_window={counters['compiles']} "
            f"cache_loads_in_window={counters['cache_loads']}")
    env.log(f"losses program={readings['program_losses']!r} "
            f"reference={readings['reference_losses']!r} "
            f"left_out={readings['leaves_left_out']}")
    ops = flops.calib_step_flops(spec, mix["samples"], mix["seq_len"],
                                 mix["steps_per_call"])
    layer = dict(counters)
    layer["step_ops"] = ops
    return {
        "attempted": counters["steps"], "failed": 0,
        "end_to_end": {"calib_step_ms": W / counters["steps"] * 1e3,
                       "calib_call_s": W / counters["calls"]},
        "layer": layer,
        "readings": {k: readings[k]
                     for k in ("loss_gap", "grad_gap", "change_gap")},
    }
