"""Self-checks of the reduction of the program's spans
(``bench/lib/spans.py``) and of the ``decode_step_ms`` reader: on a
hand-built trace whose answers are known, and on the stored chip slice,
which predates the program's spans and names.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import gzip
from pathlib import Path

import pytest

from bench import run as R
from bench.lib import spans as S
from bench.lib import trace as T

DATA = Path(__file__).with_name("data")
MS = 1_000_000                      # ns


def _ev(name, a, b):
    return T.Event(name, a * MS, b * MS)


@pytest.fixture
def hand():
    """Window [0, 100] ms. The device idles in [0, 10], [20, 30],
    [50, 60] and [70, 90]; the harness's ``step`` runs to 80 and
    ``submit`` after it; the program's ``engine.slot_write`` nests in an
    ``engine.admit``, and ``engine.sample`` covers [70, 90] only up to 85."""
    ops = [_ev("%a", 10, 20), _ev("%b", 30, 50), _ev("%c", 60, 70),
           _ev("%d", 90, 100)]
    modules = [_ev("jit_decode_step(12)", 10, 20),
               _ev("jit_prefill_chunk(7)", 30, 50),
               _ev("jit_decode_step_x(3)", 60, 70),
               _ev("jit_decode_step(12)", 85, 110)]
    program = [_ev("engine.admit", 5, 35), _ev("engine.slot_write", 22, 28),
               _ev("engine.decode", 40, 55), _ev("engine.sample", 55, 85)]
    return S.Trace([T.Device("/device:TPU:0", ops, modules)],
                   [_ev("step", 0, 80), _ev("submit", 80, 100)],
                   (0, 100 * MS), program)


@pytest.mark.parametrize("name,ms", [
    ("engine.admit", 15), ("engine.slot_write", 6), ("engine.decode", 5),
    ("engine.sample", 20), ("calibrate.prepare", 0)])
def test_idle_in_is_the_intersection(hand, name, ms):
    dev = T.first_device(hand)
    assert S.idle_in(hand, dev, name) == pytest.approx(ms / 1e3, abs=1e-12)


def test_idle_in_counts_overlapping_spans_once(hand):
    hand.program_spans.append(_ev("engine.admit", 8, 25))
    dev = T.first_device(hand)
    assert S.idle_in(hand, dev, "engine.admit") == pytest.approx(0.015)


def test_module_runs_by_name_clipped_to_window(hand):
    dev = T.first_device(hand)
    assert S.module_runs(hand, dev, "jit_decode_step") == pytest.approx(
        [0.010, 0.015])
    assert S.module_runs(hand, dev, "jit_prefill_chunk") == pytest.approx(
        [0.020])
    assert S.module_runs(hand, dev, "jit_calib_step") == []


def test_gap_labels_compose_harness_and_innermost_program_span(hand):
    dev = T.first_device(hand)
    got = S.idle_gaps(hand, dev)
    assert [g[0] for g in got] == ["submit/engine.sample",
                                   "step/engine.admit",
                                   "step/engine.slot_write",
                                   "step/engine.sample"]
    assert [g[1] for g in got] == pytest.approx([0.02, 0.01, 0.01, 0.01])
    # without program spans the labels are the harness's own
    bare = T.Trace(hand.devices, hand.host_spans, hand.window)
    assert [g[0] for g in T.idle_gaps(bare, dev)] == [
        "submit", "step", "step", "step"]


def test_decode_step_ms_reads_the_median_run(hand):
    read = R.load_reader("decode_step_ms")
    hand.devices[0].modules.append(_ev("jit_decode_step(12)", 40, 52))
    assert read({"trace": hand}) == pytest.approx(12.0)
    assert read({"trace": None}) is None


@pytest.fixture(scope="module")
def chat_profile():
    import jax

    text = gzip.open(DATA / "chat_slice.xspace.txt.gz", "rt").read()
    return jax.profiler.ProfileData.from_text_proto(text)


def test_chat_slice_reads_as_before(chat_profile):
    """The stored slice holds no program spans and its programs are
    unnamed: labels and device numbers are the harness's own, and
    ``decode_step_ms`` reads nothing."""
    base = T.load(chat_profile)
    tr = S.load(chat_profile)
    assert tr.program_spans == []
    assert (tr.devices, tr.host_spans, tr.window) == (
        base.devices, base.host_spans, base.window)
    dev, bdev = T.first_device(tr), T.first_device(base)
    assert S.idle_gaps(tr, dev, top=10 ** 6) == T.idle_gaps(
        base, bdev, top=10 ** 6)
    for name in S.PROGRAM_SPANS:
        assert S.idle_in(tr, dev, name) == 0.0
    assert R.load_reader("decode_step_ms")({"trace": tr}) is None
