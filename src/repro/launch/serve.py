"""Batched serving driver over the deployment lifecycle API.

The deployment story of the paper — program once, drift in the field,
calibrate the SRAM side-cars, serve — is owned by
``repro.deploy.Deployment``; this driver just parses flags, programs a
deployment and serves it. ``load_student`` / ``backend_scope`` /
``prefill_and_cache`` / ``generate`` remain as thin deprecation shims
over ``repro.deploy`` for callers of the old free-function API.

The ``--backend`` flag selects the substrate execution backend
(repro/substrate): ``dequant`` (float read-back fast path, the default),
``codes`` (uint8 codes resident in HBM, fused Pallas kernel) or
``codes_adc`` (ADC-faithful fidelity path). Under ``codes``/``codes_adc``
the reported ``rram_bytes`` is a measurement of the resident code arrays,
not an estimate.

CPU-scale usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 16 --gen 8 [--backend codes]

Tensor-parallel serving (codes backend only) shards the prepared tree
over a ("data", "model") mesh; on CPU, force the device count BEFORE
python starts:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --backend codes --mesh-model 4
"""
from __future__ import annotations

import argparse
from typing import Dict

import jax
import jax.numpy as jnp

from repro import deploy
from repro.configs import get_arch
from repro.launch.compile_cache import setup_compile_cache

# Re-exported lifecycle pieces (deprecated import path; use repro.deploy).
BACKENDS = deploy.BACKENDS
backend_scope = deploy.backend_scope
prefill_and_cache = deploy.prefill_and_cache
generate = deploy.generate


def load_student(cfg, seed: int = 0, adapters=None, *, backend: str = "dequant") -> Dict:
    """DEPRECATED shim over ``repro.deploy.Deployment``: program a
    deployment and return the LEGACY serve-param layout (raw per-leaf
    base + adapters merged, Algorithm 2 line 12). Same seeding as always
    — ``Deployment.program(cfg, seed)`` programs the identical deployment
    (bitwise-identical codes). ``Deployment.serve().params`` is the
    modern path and, under the codes backend, holds the PREPARED
    (padded/fused) serving tree instead — this shim keeps the raw layout
    its remaining callers index into."""
    from repro.core.calibrate import merge_adapters_for_serve

    dep = deploy.Deployment.program(cfg, seed, backend=backend)
    if adapters is not None:
        dep.adapters = adapters
    merged = merge_adapters_for_serve(dep.base, dep.adapters)
    return {"base": dep.base, "adapters": merged}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--drift-hours", type=float, default=0.0,
        help="advance the drift clock this many hours before serving",
    )
    ap.add_argument(
        "--backend", default="dequant", choices=BACKENDS,
        help="substrate execution backend (see repro/substrate)",
    )
    ap.add_argument(
        "--mesh-model", type=int, default=0,
        help="tensor-parallel degree: shard serving over a (1, N) "
             "('data', 'model') mesh (codes backend only; needs >= N "
             "devices — on CPU set XLA_FLAGS device forcing first)",
    )
    args = ap.parse_args()
    setup_compile_cache()
    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.full

    mesh = None
    if args.mesh_model > 1:
        from repro.launch.mesh import make_host_mesh

        if jax.device_count() < args.mesh_model:
            raise SystemExit(
                f"--mesh-model {args.mesh_model} needs that many devices; "
                f"only {jax.device_count()} visible (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N before launch)"
            )
        mesh = make_host_mesh((1, args.mesh_model))

    dep = deploy.Deployment.program(cfg, args.seed, backend=args.backend)
    if args.drift_hours > 0:
        dep.advance(args.drift_hours)
    session = dep.serve(mesh=mesh)
    print(session.describe())

    # independent streams for the prompt tokens and the encoder/vision
    # embeds — reusing one key correlated the draws
    key = jax.random.PRNGKey(args.seed)
    prompt_key, enc_key, patch_key = jax.random.split(key, 3)
    prompt = jax.random.randint(
        prompt_key, (args.batch, args.prompt_len), 0, cfg.vocab
    )
    enc = None
    if cfg.encoder_layers:
        enc = jax.random.normal(
            enc_key, (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16
        )
    patches = None
    if cfg.vision_tokens:
        patches = jax.random.normal(
            patch_key, (args.batch, cfg.vision_tokens, cfg.d_model),
            jnp.bfloat16,
        )
    toks, dt = session.generate(
        prompt, gen_len=args.gen, temperature=args.temperature,
        enc_embeds=enc, patch_embeds=patches,
    )
    # dt times exactly the decode steps; the first token per stream comes
    # from prefill, so decode tok/s counts gen - 1 tokens per stream
    decode_toks = args.batch * max(args.gen - 1, 0)
    tps = decode_toks / dt if dt > 0 else float("nan")
    print(f"backend={args.backend} generated {toks.shape} "
          f"(decode: {decode_toks} tok in {dt:.2f}s = {tps:.1f} tok/s)")
    print(toks[:2])


if __name__ == "__main__":
    main()
