"""Operations and bytes the benchmark charges to each piece of work.

A multiply-add is two operations. Model operations count the matmul
parameters (every projection with its rank-r side-car), attention at each
token's own position (scores and the weighted sum over the positions it
reads), and the head only for a token that yields a sampled token. Kernel
operations and bytes count a ``dora_linear`` launch at its tile-padded
extents, as the Mosaic kernel runs it.
"""
from __future__ import annotations

from bench.lib.weights import linear_shapes


def _rank(spec) -> int:
    return spec["deployment"]["dora_rank"]


def linear_macs(d_in: int, d_out: int, r: int) -> int:
    """Multiply-adds per row of one DoRA projection: the base and the
    side-car's two factors."""
    return d_in * d_out + r * (d_in + d_out)


def layer_matmul_macs(spec) -> int:
    """Multiply-adds per token of one decoder layer's projections."""
    r = _rank(spec)
    return sum(linear_macs(i, o, r) for i, o in linear_shapes(spec).values())


def head_macs(spec) -> int:
    d, v = spec["hidden_size"], spec["vocab_size"]
    if spec["tie_word_embeddings"]:
        return d * v
    return linear_macs(d, v, _rank(spec))


def attention_macs(spec, pos: int) -> int:
    """Multiply-adds of one layer's attention for the token at 0-based
    position ``pos``: scores and weighted sum over ``pos + 1`` keys."""
    return 2 * spec["num_attention_heads"] * spec["head_dim"] * (pos + 1)


def _attn_sum(spec, lo: int, hi: int) -> int:
    """Sum of ``attention_macs`` over positions [lo, hi)."""
    n = hi - lo
    keys = n * (lo + hi + 1) // 2          # sum of (pos + 1)
    return 2 * spec["num_attention_heads"] * spec["head_dim"] * keys


def prefill_flops(spec, prompt_len: int) -> int:
    """Operations to prefill a prompt and yield its first token."""
    n_layers = spec["num_hidden_layers"]
    macs = n_layers * (prompt_len * layer_matmul_macs(spec)
                       + _attn_sum(spec, 0, prompt_len))
    return 2 * (macs + head_macs(spec))


def decode_flops(spec, pos: int) -> int:
    """Operations to decode one token whose input sits at position
    ``pos``."""
    n_layers = spec["num_hidden_layers"]
    macs = n_layers * (layer_matmul_macs(spec) + attention_macs(spec, pos))
    return 2 * (macs + head_macs(spec))


def calib_step_flops(spec, batch: int, seq: int, steps_per_call: int) -> int:
    """Operations one calibration step requires.

    Each layer of the student runs forward on the teacher's input features
    and backward to its side-cars: the backward needs the gradient with
    respect to the layer's input activations (one matmul per projection,
    as many operations as the forward's) plus the side-cars' own gradients,
    and the attention backward twice the attention forward. Each DoRA
    column norm of ``W + A B`` costs ``2 d_in d_out r`` multiply-adds
    forward (``W^T A`` and ``A B``) and twice that backward. An untied head
    adds its own term on the cached head input. The teacher's forward runs
    once per call and is shared by the call's steps. Recomputation for
    rematerialisation does not count.
    """
    n_layers = spec["num_hidden_layers"]
    r = _rank(spec)
    tokens = batch * seq
    proj = sum(i * o for i, o in linear_shapes(spec).values())
    side = sum(r * (i + o) for i, o in linear_shapes(spec).values())
    attn = batch * _attn_sum(spec, 0, seq)
    norm = sum(2 * i * o * r for i, o in linear_shapes(spec).values())
    fwd = tokens * (proj + side) + attn
    bwd = tokens * (proj + 2 * side) + 2 * attn
    macs = n_layers * (fwd + bwd + 3 * norm)
    if not spec["tie_word_embeddings"]:
        d, v = spec["hidden_size"], spec["vocab_size"]
        head = tokens * linear_macs(d, v, r)
        macs += 2 * head + tokens * r * (d + 2 * v) + 3 * 2 * d * v * r
    teacher = n_layers * (tokens * proj + attn)     # no side-cars
    if not spec["tie_word_embeddings"]:
        teacher += tokens * spec["hidden_size"] * spec["vocab_size"]
    return 2 * macs + 2 * teacher // steps_per_call


def kernel_cost(m: int, k: int, n: int, r: int, *, x_bytes: int = 2,
                out_bytes: int = 4):
    """``(operations, bytes)`` of one ``dora_linear`` launch over padded
    extents: an (m, k) activation against a (k, n) uint8 code pair with a
    float32 scale, side-car factors A (k, r) and B (r, n) and merged
    magnitude per column, writing an (m, n) output."""
    ops = 2 * (m * k * n + m * k * r + m * r * n)
    nbytes = (m * k * x_bytes + 2 * k * n + 4 * n * 2 + 4 * (k * r + r * n)
              + m * n * out_bytes)
    return ops, nbytes
