"""Open-loop serving: requests arrive on a schedule drawn from the seed and
are served by the program's ``ServeEngine`` over ``Deployment.serve()``
(codes backend), whatever the engine's backlog.

The loop submits every request whose due time has passed, then runs one
``engine.step()``, and repeats; it stamps each output token with the host
clock after the call that produced it. Time to first token runs from the
request's due time; a request due in the window that has no first token
when the window closes counts at its wait so far.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench.lib import flops, reference, stats, traffic, weights
from bench.lib import program as prog


@dataclasses.dataclass
class Tracked:
    plan: traffic.Planned
    req: object = None
    submitted: Optional[float] = None
    slot_at: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)


def _buckets(engine) -> List[int]:
    b, out = engine.min_bucket, []
    while b <= engine.prefill_chunk:
        out.append(b)
        b *= 2
    return out


def _slice_counters(engine) -> dict:
    return {"ticks": engine.tick, "prefill_chunks": engine.prefill_chunks,
            "decode_tokens": engine.decode_tokens}


def setup(env, spec, mix, seed, accum="f32"):
    """Weights from the seed, the deployment, the serving session, and an
    engine with every shape of this mix compiled. Returns a dict."""
    import jax

    from repro.deploy import Deployment, ServeEngine

    cfg = prog.model_config(spec)
    w, a, _ = weights.make(spec, seed, teacher=False, adapters="served")
    jax.block_until_ready((w, a))
    codes, ads, _ = prog.to_program(cfg, w, a)
    key = weights.seed_key(seed)
    dep = Deployment(cfg, "codes", None, codes, ads, key,
                     jax.random.fold_in(key, 1))
    session = dep.serve(accum=accum)
    eng = mix["engine"]
    engine = ServeEngine(session, max_slots=eng["max_slots"],
                         max_len=eng["max_len"],
                         prefill_chunk=eng["prefill_chunk"])
    # one request per slot, every chunk bucket among them, each decoding
    # a few tokens: compiles every chunk width, the first-token and slot
    # write paths of every slot and the batched decode step
    lens = _buckets(engine)
    lens += [engine.prefill_chunk + 1] * (engine.max_slots - len(lens))
    rng = np.random.default_rng([int(seed), 99])
    warm = [engine.submit(rng.integers(0, spec["vocab_size"], n)
                          .astype(np.int32), max_new=3) for n in lens]
    engine.run()
    if not all(r.done for r in warm):
        raise RuntimeError("warm-up requests did not finish")
    jax.block_until_ready(engine.cache)
    return {"cfg": cfg, "w": w, "a": a, "dep": dep, "session": session,
            "engine": engine}


def run_window(env, state, spec, mix, seed, seconds, rate=None):
    """The open loop. Returns the tracked requests and the loop's
    counters. A traced run records the window's last ``trace_s`` seconds;
    the counters say how many engine ticks, prefill chunks and decode
    tokens fell in that slice, and how many requests were live in it."""
    engine = state["engine"]
    plan = traffic.schedule(mix, seed, lead=mix["lead_s"], window=seconds,
                            vocab=spec["vocab_size"], rate=rate)
    reqs = [Tracked(p) for p in plan]
    live: List[Tracked] = []
    t_start = time.perf_counter() + mix["lead_s"]
    clock = lambda: time.perf_counter() - t_start
    i = 0
    busy = {"window": 0.0}
    dec0 = None
    compiles0 = loads0 = None
    tracing_from = seconds - min(mix["trace_s"], seconds)
    traced = None               # engine counters when the trace began
    live_traced = []            # live requests at each traced loop turn
    worst = {"step_s": 0.0, "step_at": None, "oversleep_s": 0.0,
             "oversleep_at": None}

    def observe(now):
        for tr in live:
            r = tr.req
            if tr.slot_at is None and (r.slot is not None or r.done):
                tr.slot_at = now
            new = len(r.tokens) - len(tr.times)
            if new > 0:
                tr.times.extend([now] * new)
        live[:] = [tr for tr in live if not tr.req.done]

    while True:
        t = clock()
        if t >= seconds:
            break
        if dec0 is None and t >= 0.0:
            dec0 = engine.decode_seconds
            compiles0, loads0 = env.compiles, env.cache_loads
        if tracing_from is not None and t >= tracing_from:
            env.trace_start()
            tracing_from = None
            if env.tracing:
                traced = _slice_counters(engine)
        if traced is not None:
            live_traced.append(len(live))
        while i < len(reqs) and reqs[i].plan.due <= t:
            tr = reqs[i]
            with env.span("submit"):
                t0 = clock()
                tr.req = engine.submit(tr.plan.prompt,
                                       max_new=tr.plan.max_new)
                t1 = clock()
            tr.submitted = t0
            if t0 >= 0.0:
                busy["window"] += t1 - t0
            live.append(tr)
            observe(t1)
            i += 1
        if live:
            with env.span("step"):
                t0 = clock()
                engine.step()
                t1 = clock()
            if t0 >= 0.0:
                busy["window"] += t1 - t0
                if t1 - t0 > worst["step_s"]:
                    worst.update(step_s=t1 - t0, step_at=t0)
            observe(t1)
        else:
            nxt = min(reqs[i].plan.due if i < len(reqs) else seconds, seconds)
            if tracing_from is not None:
                nxt = min(nxt, tracing_from)
            with env.span("arrival_wait"):
                time.sleep(max(0.0, nxt - clock()))
            over = clock() - nxt
            if nxt >= 0.0 and over > worst["oversleep_s"]:
                worst.update(oversleep_s=over, oversleep_at=nxt)
    window = clock()
    in_trace = {}
    if traced is not None:
        now = _slice_counters(engine)
        in_trace = {k: now[k] - traced[k] for k in now}
        in_trace["mean_live"] = (sum(live_traced) / len(live_traced)
                                 if live_traced else 0.0)
    env.trace_stop()
    if dec0 is None:
        raise RuntimeError("the window never opened")
    return reqs, {
        "window_s": window,
        "busy_s": busy["window"],
        "decode_s": engine.decode_seconds - dec0,
        "compiles": env.compiles - compiles0,
        "cache_loads": env.cache_loads - loads0,
        "worst": worst,
        "in_trace": in_trace,
    }


def measure(spec, reqs, counters, seconds):
    """End-to-end and per-layer numbers of one window."""
    W = seconds
    ttft, itl, queue, late = [], [], [], []
    out_tokens = 0
    prefill_ops = decode_ops = 0
    for tr in reqs:
        due = tr.plan.due
        if 0.0 <= due < W:
            late.append((tr.submitted if tr.submitted is not None else W)
                        - due)
            first = tr.times[0] if tr.times else None
            ttft.append((first if first is not None and first < W else W)
                        - due)
            slot = tr.slot_at
            queue.append((slot if slot is not None and slot < W else W)
                          - due)
        p = tr.plan.prompt.size
        for j, t in enumerate(tr.times):
            if not 0.0 <= t < W:
                continue
            out_tokens += 1
            if j == 0:
                prefill_ops += flops.prefill_flops(spec, p)
            else:
                itl.append(t - tr.times[j - 1])
                decode_ops += flops.decode_flops(spec, p + j - 1)
    return {
        "ttft_s": ttft, "itl_s": itl, "queue_s": queue, "late_s": late,
        "out_tokens": out_tokens, "prefill_ops": prefill_ops,
        "decode_ops": decode_ops,
    }


def sample_for_check(reqs, seed, target_tokens):
    """Finished requests drawn from the seed, the longest among them,
    until ``target_tokens`` served tokens are covered."""
    done = [tr for tr in reqs if tr.req is not None and tr.req.done]
    if not done:
        return []
    done.sort(key=lambda tr: tr.plan.prompt.size + len(tr.req.tokens))
    pick = [done.pop()]
    rng = np.random.default_rng([int(seed), 2])
    order = rng.permutation(len(done))
    for k in order:
        if sum(len(tr.req.tokens) for tr in pick) >= target_tokens:
            break
        pick.append(done[k])
    return pick


def check(spec, w, a, picked, length, lowp=None):
    """The gaps, over every served token of the picked requests, by which
    the token's reference logit lies below the reference's best: their
    mean (the number compared) and their widest."""
    seqs = [(tr.plan.prompt, np.asarray(tr.req.tokens, np.int32))
            for tr in picked]
    gaps = reference.served_gaps(spec, w, a, seqs, length, lowp=lowp)
    flat = np.concatenate(gaps)
    return {"max_gap": float(flat.max()), "mean_gap": float(flat.mean()),
            "tokens": int(flat.size), "requests": len(seqs)}


def run(env, spec, mix, seed, seconds):
    """One run of an open-loop cell. Returns the harness's result dict."""
    import jax

    with env.phase("setup"):
        state = setup(env, spec, mix, seed)
    reqs, counters = run_window(env, state, spec, mix, seed, seconds)
    m = measure(spec, reqs, counters, counters["window_s"])
    env.memory_peak()
    attempted = sum(1 for tr in reqs if 0.0 <= tr.plan.due < seconds)
    picked = sample_for_check(reqs, seed, mix["check_tokens"])
    w, a = state["w"], state["a"]
    del state
    jax.clear_caches()
    got = (check(spec, w, a, picked, mix["engine"]["max_len"])
           if picked else {})
    env.log(f"check requests={got.get('requests')} "
            f"tokens={got.get('tokens')} max_gap={got.get('max_gap')!r}")
    readings = {"mean_gap": got.get("mean_gap")}
    W = counters["window_s"]
    lat = m["late_s"] or [0.0]
    env.log(f"generator lateness_ms p50={stats.median(lat) * 1e3!r} "
            f"max={max(lat) * 1e3!r} n={len(m['late_s'])}")
    env.log(f"window_s={W!r} busy_s={counters['busy_s']!r} "
            f"decode_s={counters['decode_s']!r} "
            f"compiles_in_window={counters['compiles']} "
            f"cache_loads_in_window={counters['cache_loads']} "
            f"requests_due={attempted} ttft_samples={len(m['ttft_s'])} "
            f"itl_samples={len(m['itl_s'])} out_tokens={m['out_tokens']}")
    wst = counters["worst"]
    env.log(f"longest step_ms={wst['step_s'] * 1e3!r} at_s={wst['step_at']!r}"
            f" longest oversleep_ms={wst['oversleep_s'] * 1e3!r} "
            f"at_s={wst['oversleep_at']!r}")
    if counters["in_trace"]:
        env.log("traced slice " + " ".join(
            f"{k}={v!r}" for k, v in counters["in_trace"].items()))
    e2e = {"output_tok_per_s": m["out_tokens"] / W}
    layer = dict(counters)
    layer.update(m)
    unserved = sum(1 for tr in reqs if 0.0 <= tr.plan.due < seconds
                   and not tr.times)
    env.log(f"due_in_window_without_first_token={unserved}")
    return {
        "attempted": attempted, "failed": 0, "end_to_end": e2e,
        "layer": layer, "readings": readings,
    }
