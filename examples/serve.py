"""Batched serving: prefill a batch of prompts, then decode with KV caches.

    PYTHONPATH=src python examples/serve.py [--arch tinyllama-1.1b] \
        [--batch 4] [--prompt-len 32] [--gen 16]

Uses the smoke-size variant of any assigned arch (the full configs need a
pod).  Demonstrates the serve_step path the decode_32k / long_500k
dry-run cells lower: prefill -> argmax decode loop against the cache
(incl. SSM-state decode for mamba/jamba).

NOTE: this (and launch/serve.py) serves the LM stack.  The Sketch-and-
Scale serving counterpart — incremental ingest + warm re-embed +
out-of-sample transform() — is examples/sns_service.py on top of
core.service.SnsService.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.configs import ARCH_IDS, get_config                 # noqa: E402
from repro.models import model as model_mod                    # noqa: E402
from repro.train.steps import (make_prefill_step,              # noqa: E402
                               make_decode_step)


def main():
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    print(f"[model] {args.arch} (smoke config: {cfg.num_layers}L "
          f"d{cfg.d_model}, vocab {cfg.vocab_size})")
    params = model_mod.init_params(jax.random.key(0), cfg)

    cache_len = args.prompt_len + args.gen
    prefill = jax.jit(make_prefill_step(cfg, cache_len))
    decode = jax.jit(make_decode_step(cfg))

    key = jax.random.key(1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            key, (args.batch, cfg.num_prefix, cfg.d_model), cfg.pdtype)
    if cfg.encoder_layers:
        batch["src_embeds"] = 0.02 * jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model), cfg.pdtype)

    t0 = time.perf_counter()
    logits, state = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"[prefill] batch={args.batch} len={args.prompt_len} "
          f"-> {t_prefill * 1e3:.1f} ms")

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    outs = [tok]
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        logits, state = decode(params, tok, state)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    gen = np.concatenate([np.asarray(t) for t in outs], axis=1)
    print(f"[decode] {args.gen - 1} steps -> "
          f"{t_decode * 1e3 / max(args.gen - 1, 1):.1f} ms/token "
          f"({args.batch * (args.gen - 1) / t_decode:.0f} tok/s batch)")
    print(f"[sample] first sequence token ids: {gen[0].tolist()}")


if __name__ == "__main__":
    main()
