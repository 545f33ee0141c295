"""Error-feedback int8 gradient compression (optim/compress.py).

The EF guarantee this pins: with a CONSTANT gradient g, the compressed
updates telescope — q_t = g + r_{t-1} - r_t — so the running mean of
what ``allreduce_compressed`` emits differs from g by exactly
(r_0 - r_T)/T. A single quantized step is biased (that's what makes the
test meaningful); the bias of the ACCUMULATED trajectory shrinks as 1/T.
Runs on one device: shard_map over a size-1 "data" axis binds the axis
name ``allreduce_compressed`` psums over.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.compress import allreduce_compressed, compress, init_residual


def _one_device_step():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def body(g, r):
        return allreduce_compressed(g, r, "data")

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
    )


def test_compress_returns_codes_scales_residual():
    g = {"a": jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)}
    r = init_residual(g)
    codes, scales, new_r = compress(g, r)
    assert codes["a"].dtype == jnp.int8
    assert scales["a"].shape == ()
    assert new_r["a"].shape == g["a"].shape
    # dequantized codes + residual reconstruct the input exactly
    recon = codes["a"].astype(jnp.float32) * scales["a"] + new_r["a"]
    np.testing.assert_allclose(np.asarray(recon), np.asarray(g["a"]),
                               rtol=0, atol=1e-6)


def test_error_feedback_shrinks_accumulated_bias():
    step = _one_device_step()
    key = jax.random.PRNGKey(0)
    g = {"w": jax.random.normal(key, (16, 16)) * 0.37}
    r = init_residual(g)

    # one quantized step IS biased — otherwise the property is vacuous
    q1, _ = step(g, r)
    e1 = float(np.max(np.abs(np.asarray(q1["w"]) - np.asarray(g["w"]))))
    assert e1 > 0

    biases = []
    acc = jnp.zeros_like(g["w"])
    r = init_residual(g)
    for t in range(1, 33):
        q, r = step(g, r)
        acc = acc + q["w"]
        biases.append(float(np.max(np.abs(np.asarray(acc / t - g["w"])))))
    # telescoping: accumulated bias after T steps = |r_0 - r_T| / T
    assert biases[31] < biases[3] < biases[0]
    # and it tracks the 1/T envelope, not just "eventually smaller"
    assert biases[31] <= biases[7] / 2 + 1e-7


def test_allreduce_mean_is_exact_when_lossless():
    # absmax 127 makes the scale exactly 1.0: integer grads quantize
    # losslessly -> psum mean must be bitwise the input, residual zero
    step = _one_device_step()
    g = {"w": jnp.asarray([[127.0, -64.0], [32.0, 0.0]], jnp.float32)}
    q, r = step(g, init_residual(g))
    np.testing.assert_array_equal(np.asarray(q["w"]), np.asarray(g["w"]))
    np.testing.assert_array_equal(np.asarray(r["w"]), np.zeros((2, 2)))
