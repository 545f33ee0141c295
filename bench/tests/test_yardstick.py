"""Self-checks of the benchmark's yardstick: peaks, operation counts,
order statistics, the traffic generator and the trace reduction (on a
slice of a trace recorded on a TPU v5e).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import gzip
import json
import statistics
from pathlib import Path

import numpy as np
import pytest

from bench.lib import flops, stats, traffic
from bench.lib import trace as T
from bench.lib.peaks import UnknownDevice, peaks
from bench.tests import tiny

DATA = Path(__file__).with_name("data")


# -- peaks -------------------------------------------------------------------

def test_v5e_peaks_as_published():
    p = peaks("TPU v5 lite")
    assert p == {"bf16_flops": 197e12, "int8_ops": 393e12,
                 "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")


# -- operation counts ----------------------------------------------------------

def _hand_layer_macs(s):
    """Per-token multiply-adds of one layer, projection by projection."""
    d, dff, r = s["hidden_size"], s["intermediate_size"], \
        s["deployment"]["dora_rank"]
    q = s["num_attention_heads"] * s["head_dim"]
    kv = s["num_key_value_heads"] * s["head_dim"]
    mats = [(d, q), (d, kv), (d, kv), (q, d), (d, dff), (d, dff), (dff, d)]
    return sum(i * o + r * i + r * o for i, o in mats)


@pytest.mark.parametrize("tied", [True, False])
def test_prefill_and_decode_counts_match_hand_counts(tied):
    s = tiny.spec(tied)
    L, h, hd, d, v = (s["num_hidden_layers"], s["num_attention_heads"],
                      s["head_dim"], s["hidden_size"], s["vocab_size"])
    r = s["deployment"]["dora_rank"]
    head = d * v if tied else d * v + r * (d + v)
    prompt = 37
    macs = 0
    for pos in range(prompt):
        for _ in range(L):
            macs += _hand_layer_macs(s)
            macs += h * hd * (pos + 1) * 2       # scores + weighted sum
    macs += head
    assert flops.prefill_flops(s, prompt) == 2 * macs
    pos = 50
    want = 2 * (L * (_hand_layer_macs(s) + 2 * h * hd * (pos + 1)) + head)
    assert flops.decode_flops(s, pos) == want


def test_calibration_step_counts_forward_backward_and_teacher():
    s = tiny.spec(True)
    b, q, steps = 4, 8, 20
    L, r = s["num_hidden_layers"], s["deployment"]["dora_rank"]
    mats = [(64, 64), (64, 32), (64, 32), (64, 64), (64, 128), (64, 128),
            (128, 64)]
    tokens = b * q
    attn = b * sum(2 * 4 * 16 * (p + 1) for p in range(q))
    proj = sum(i * o for i, o in mats)
    side = sum(r * (i + o) for i, o in mats)
    norm = sum(2 * i * o * r for i, o in mats)
    fwd = tokens * (proj + side) + attn
    bwd = tokens * (proj + 2 * side) + 2 * attn
    teacher = L * (tokens * proj + attn)
    want = 2 * L * (fwd + bwd + 3 * norm) + 2 * teacher // steps
    assert flops.calib_step_flops(s, b, q, steps) == want


def test_kernel_cost_hand_count():
    ops, nbytes = flops.kernel_cost(8, 2048, 4096, 24)
    assert ops == 2 * (8 * 2048 * 4096 + 8 * 2048 * 24 + 8 * 24 * 4096)
    assert nbytes == (8 * 2048 * 2 + 2 * 2048 * 4096 + 8 * 4096
                      + 4 * (2048 * 24 + 24 * 4096) + 8 * 4096 * 4)


# -- order statistics ----------------------------------------------------------

def test_percentile_matches_inclusive_quantiles():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    q = statistics.quantiles(xs, n=100, method="inclusive")
    for p in (10, 50, 90, 95):
        assert stats.percentile(xs, p) == pytest.approx(q[p - 1])
    assert stats.median(xs) == statistics.median(xs)


# -- traffic ---------------------------------------------------------------------

def test_every_seed_gets_the_same_window_set_in_its_own_order():
    mix = json.loads((tiny.BENCH / "traffic" / "chat.json").read_text())
    a = traffic.schedule(mix, 1, lead=6, window=30, vocab=1000)
    b = traffic.schedule(mix, 2 ** 40 + 3, lead=6, window=30, vocab=1000)
    again = traffic.schedule(mix, 1, lead=6, window=30, vocab=1000)
    assert [(p.due, p.prompt.size, p.max_new) for p in a] == \
        [(p.due, p.prompt.size, p.max_new) for p in again]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, again))
    rate = mix["rate_per_s"]
    for plan in (a, b):
        win = [p for p in plan if 0 <= p.due < 30]
        assert len(win) == round(rate * 30)
        assert len(plan) - len(win) == round(rate * 6)
        assert plan[0].due > -6 and plan[-1].due < 30
        gaps = np.diff([p.due for p in plan])
        assert gaps.min() > 0 and len(set(gaps.round(9))) > len(gaps) // 2
        for p in plan:
            assert 32 <= p.prompt.size <= 768 and 16 <= p.max_new <= 256
            assert p.prompt.min() >= 0 and p.prompt.max() < 1000
    # the window's sizes are one set for every seed, in another order
    wa = [p for p in a if p.due >= 0]
    wb = [p for p in b if p.due >= 0]
    for key in (lambda p: p.prompt.size, lambda p: p.max_new):
        assert sorted(map(key, wa)) == sorted(map(key, wb))
        assert list(map(key, wa)) != list(map(key, wb))
    ga, gb = np.diff([p.due for p in wa]), np.diff([p.due for p in wb])
    assert not np.allclose(ga, gb)
    assert any(not np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


def test_lognormal_set_has_the_stated_median():
    lens = traffic.lengths({"median": 160, "sigma": 0.8, "min": 32,
                            "max": 768}, 1001)
    assert stats.median(lens) == 160
    assert lens.min() >= 32 and lens.max() <= 768


# -- trace reduction -----------------------------------------------------------

@pytest.fixture(scope="module")
def chat_slice():
    """80 ms of ``qwen3-1.7b.chat`` traced on a TPU v5e (decode ticks and
    one admitting request), cut by ``bench/tools/slice_trace.py``."""
    import jax

    text = gzip.open(DATA / "chat_slice.xspace.txt.gz", "rt").read()
    return T.load(jax.profiler.ProfileData.from_text_proto(text))


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over interval ends (another way to union)."""
    edges = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy / 1e9


def test_busy_and_idle_share(chat_slice):
    dev = T.first_device(chat_slice)
    lo, hi = chat_slice.window
    assert T.window_seconds(chat_slice) == pytest.approx(0.08)
    busy = T.busy_seconds(chat_slice, dev)
    assert busy == pytest.approx(_sweep_busy(dev.ops, lo, hi), abs=1e-9)
    assert 0 < busy < T.window_seconds(chat_slice)
    gaps = T.idle_gaps(chat_slice, dev, top=10 ** 6)
    assert sum(g for _, g in gaps) == pytest.approx(
        T.window_seconds(chat_slice) - busy, abs=1e-9)
    assert {label for label, _ in gaps} <= {"step", "submit", "none"}


def test_kernel_launches_by_program(chat_slice):
    dev = T.first_device(chat_slice)
    kinds = T.module_kinds(chat_slice, dev, 28, 8, 1024)
    assert sorted(kinds.values()) == ["decode", "prefill"]
    got = {}
    for x in T.launches(chat_slice, dev):
        key = (kinds[x.module], x.kernel, x.m, x.k, x.n, x.r)
        got[key] = got.get(key, 0) + 1
    assert got == {
        ("prefill", "dora_linear_gemv", 32, 2048, 4096, 24): 84,
        ("prefill", "dora_linear_gemv", 32, 2048, 2048, 8): 84,
        ("prefill", "dora_linear_gemv", 32, 2048, 12288, 16): 84,
        ("prefill", "dora_linear_gemv", 32, 6144, 2048, 8): 84,
        ("decode", "dora_linear_gemv", 8, 2048, 4096, 24): 45,
        ("decode", "dora_linear_gemv", 8, 2048, 2048, 8): 45,
        ("decode", "dora_linear_gemv", 8, 2048, 12288, 16): 45,
        ("decode", "dora_linear_gemv", 8, 6144, 2048, 8): 45,
    }


def test_launch_time_includes_operand_staging(chat_slice):
    dev = T.first_device(chat_slice)
    own = sum((e.end - e.start) / 1e9 for e in dev.ops
              if T.parse_launch(e.name) is not None
              and chat_slice.window[0] <= e.start
              and e.end <= chat_slice.window[1])
    with_staging = sum(x.seconds for x in T.launches(chat_slice, dev))
    assert with_staging > own


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_roofline_share_is_under_peak(chat_slice, kind):
    dev = T.first_device(chat_slice)
    kinds = T.module_kinds(chat_slice, dev, 28, 8, 1024)
    mine = [x for x in T.launches(chat_slice, dev)
            if kinds[x.module] == kind]
    share = T.roofline_share(
        mine, 197e12, 819e9, lambda m, k, n, r, xb, ob: flops.kernel_cost(
            m, k, n, r, x_bytes=xb, out_bytes=ob))
    assert 0.2 < share < 1.0


def test_breakdown_lists_self_time(chat_slice):
    dev = T.first_device(chat_slice)
    top = T.top_ops(chat_slice, dev)
    assert 0 < len(top) <= 10
    assert all(top[i][1] >= top[i + 1][1] for i in range(len(top) - 1))
    total = sum(T.self_seconds(chat_slice, dev).values())
    # self times add up to the busy time: nesting is not counted twice
    assert total == pytest.approx(T.busy_seconds(chat_slice, dev), rel=1e-6)
    assert not any(name.startswith("%while") for name, _ in top)
