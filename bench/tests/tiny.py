"""A tiny configuration and a CPU stand-in for the harness's ``Env``, so
that the benchmark's drivers run end to end in the test suite (Pallas
kernels in interpret mode)."""
from __future__ import annotations

import contextlib
import copy
import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def spec(tied=True):
    s = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    s.update(name="tiny", hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256,
             tie_word_embeddings=tied)
    s["deployment"] = dict(s["deployment"], dora_rank=4)
    return s


def mix(name, **over):
    m = copy.deepcopy(json.loads((BENCH / "traffic" / f"{name}.json")
                                 .read_text()))
    m.update(over)
    return m


class CpuEnv:
    """The harness's ``Env`` without a chip or a profiler."""

    def __init__(self):
        self.tracing = False
        self.compiles = 0
        self.cache_loads = 0
        self.setup_s = None
        self.peak_bytes = None
        self.t0 = time.perf_counter()
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)

    @contextlib.contextmanager
    def phase(self, name):
        yield
        self.setup_s = time.perf_counter() - self.t0

    def span(self, name):
        return contextlib.nullcontext()

    def trace_start(self):
        pass

    def trace_stop(self):
        pass

    def memory_peak(self):
        self.peak_bytes = 0
