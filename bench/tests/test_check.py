"""The correctness check of each cell, driven end to end at a size the CPU
holds (``bench/tests/tiny.py``; Pallas kernels in interpret mode): a sound
run comes out correct, and a run whose timed path is broken underneath, or
whose program is replaced by the lower-precision control, does not.

Each run skips the harness's look for a chip (``bench.run.main``) and calls
the rest of it (``bench.run.run_cell``) with the cell's own limits.
"""
import json

import jax.numpy as jnp
import pytest

from bench import run as R
from bench.lib.peaks import peaks
from bench.tests import tiny

BENCH = json.loads((R.ROOT / "BENCHMARK.json").read_text())
PEAK = peaks("TPU v5 lite")
CHAT = "qwen3-1.7b.chat"
CALIB = "qwen3-1.7b.calib10"


def limits(cell):
    return json.loads((R.BENCH / "limits" / f"{cell}.json").read_text())


def chat_mix():
    return tiny.mix(
        "chat", rate_per_s=4.0, lead_s=1.0,
        prompt={"median": 24, "sigma": 0.8, "min": 4, "max": 80},
        output={"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        engine={"max_slots": 4, "max_len": 96, "prefill_chunk": 16},
        check_tokens=40)


def calib_mix():
    return tiny.mix("calib10", samples=4, seq_len=8, steps_per_call=3)


def run_chat(seed=11):
    return R.run_cell(tiny.CpuEnv(), BENCH, CHAT, tiny.spec(), chat_mix(),
                      limits(CHAT), PEAK, seed, 2.0)


def run_calib(seed=7):
    return R.run_cell(tiny.CpuEnv(), BENCH, CALIB, tiny.spec(False),
                      calib_mix(), limits(CALIB), PEAK, seed, 0.5)


def test_sound_serving_run_is_correct():
    out = run_chat()
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def test_altered_token_is_caught(monkeypatch):
    """A served token altered where it is produced: the sampler returns
    the token after the greedy one."""
    from repro.deploy import serving

    real = serving._next_token

    def altered(logits, temperature, key):
        tok, key = real(logits, temperature, key)
        return (tok + 1) % logits.shape[-1], key

    monkeypatch.setattr(serving, "_next_token", altered)
    out = run_chat()
    assert not out["correct"], out["checks"]


def test_sound_calibration_run_is_correct():
    out = run_calib()
    assert out["correct"], out["checks"]


def test_unchanged_state_is_caught(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.deploy import deployment

    real = deployment.jit_calib_step

    def frozen(step, **kw):
        run = real(step, **kw)

        def go(state, *args):
            _, *rest = run(state, *args)
            return (state, *rest)

        return go

    monkeypatch.setattr(deployment, "jit_calib_step", frozen)
    out = run_calib()
    assert not out["correct"], out["checks"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch):
    """A step that takes its mean over half of the batch."""
    from repro.deploy import deployment

    real = deployment.Deployment._calibration_batch

    def half(self, batch_or_samples, seq_len):
        batch = real(self, batch_or_samples, seq_len)
        n = batch["tokens"].shape[0] // 2
        return {k: v[:n] for k, v in batch.items()}

    monkeypatch.setattr(deployment.Deployment, "_calibration_batch", half)
    out = run_calib()
    assert not out["correct"], out["checks"]


def test_int8_reference_in_the_programs_place_is_caught():
    """The calibration control: the reference with int8 matmul operands
    stands in for the program."""
    from bench.drivers import calibrate as D

    env = tiny.CpuEnv()
    spec = tiny.spec(False)
    state = D.setup(env, spec, calib_mix(), 7)
    del state["dep"]
    got = D.check(spec, state, lowp="int8")
    lim = limits(CALIB)
    assert any(got[k] > lim[k] for k in lim), got
