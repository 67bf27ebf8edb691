"""End-to-end driver: train a small LM with the full production stack —
checkpointed Trainer, cosine schedule, Count-Sketch gradient compression
(FetchSGD-style, the paper's data structure as a distributed-training
optimization), and the SnS activation monitor.

    PYTHONPATH=src python examples/train_lm.py                # quick (~2 min)
    PYTHONPATH=src python examples/train_lm.py --steps 300 --d-model 512 \
        --layers 12     # ~100M-class run (CPU: slow but it is the real loop)
    PYTHONPATH=src python examples/train_lm.py --sketch-grads  # compressed
"""
import argparse
import sys

sys.path.insert(0, "src")

import dataclasses                                             # noqa: E402
import jax                                                     # noqa: E402
import numpy as np                                             # noqa: E402

from repro.data import zipf_token_stream                       # noqa: E402
from repro.models.config import ModelConfig                    # noqa: E402
from repro.optim import (SketchCompressConfig,                 # noqa: E402
                         sketch_compress_init, compress_and_reduce)
from repro.train.steps import TrainStepConfig                  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig         # noqa: E402


def main():
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--sketch-grads", action="store_true",
                    help="Count-Sketch gradient compression demo")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = ModelConfig(
        arch_id="example-lm", family="dense",
        num_layers=args.layers, d_model=args.d_model,
        num_heads=max(args.d_model // 32, 2), num_kv_heads=2,
        d_ff=args.d_model * 3, vocab_size=args.vocab, head_dim=32)
    n_params = cfg.param_count()
    print(f"[model] {n_params / 1e6:.1f}M params, {args.layers}L "
          f"d{args.d_model}")

    tcfg = TrainStepConfig(peak_lr=args.lr, warmup_steps=10,
                           total_steps=args.steps, q_chunk=64)
    rc = TrainerConfig(total_steps=args.steps, ckpt_every=20,
                       ckpt_dir=args.ckpt_dir, log_every=10,
                       monitor_activations=True)

    def batch_fn(step):
        return zipf_token_stream(jax.random.key(step), args.batch,
                                 args.seq, args.vocab)

    if args.sketch_grads:
        print("[optim] Count-Sketch compressed gradients "
              "(sketch all-reduced instead of the dense gradient)")
        _demo_sketch_grads(cfg, tcfg, args, batch_fn)
        return

    tr = Trainer(cfg, tcfg, rc, batch_fn)
    if tr.start_step:
        print(f"[resume] from checkpoint step {tr.start_step}")
    out = tr.run()
    first = out["metrics"][0]["loss"] if out["metrics"] else float("nan")
    last = out["metrics"][-1]["loss"] if out["metrics"] else float("nan")
    print(f"[train] steps={out['final_step']} wall={out['wall_s']:.1f}s "
          f"loss {first:.3f} -> {last:.3f}")
    rep = out.get("activation_report", {})
    print(f"[sns-monitor] representation-space HHs={rep.get('hh_count')} "
          f"top1_frac={rep.get('hh_top1_frac', 0):.3f} "
          f"tokens_seen={rep.get('tokens_seen')}")


def _demo_sketch_grads(cfg, tcfg, args, batch_fn):
    """Manual loop: grads -> sketch -> (psum in multi-host) -> top-k apply."""
    from repro.models import model as model_mod
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    params = model_mod.init_params(jax.random.key(0), cfg)
    ccfg = SketchCompressConfig(rows=8, log2_cols=16, top_k=50_000)
    cstate = sketch_compress_init(params, ccfg)
    ocfg = AdamWConfig(lr=args.lr)
    ostate = adamw_init(params)
    n = cfg.param_count()
    wire_dense = 2 * n
    wire_sketch = 4 * ccfg.rows * (1 << ccfg.log2_cols)
    print(f"[wire] dense grad all-reduce: {wire_dense / 2**20:.1f} MiB/step"
          f"  sketch: {wire_sketch / 2**20:.1f} MiB/step "
          f"({wire_dense / wire_sketch:.0f}x less)")

    @jax.jit
    def grad_fn(p, batch):
        def loss(p):
            return model_mod.forward_train(cfg, p, batch, q_chunk=64)
        return jax.value_and_grad(loss, has_aux=True)(p)

    for step in range(args.steps):
        batch = batch_fn(step)
        (loss, _), grads = grad_fn(params, batch)
        upd, cstate, density = compress_and_reduce(grads, cstate, ccfg)
        params, ostate, _ = adamw_update(upd, ostate, params, ocfg)
        if step % 10 == 0:
            print(f"  step {step:4d} loss {float(loss):.3f} "
                  f"density {float(density):.4f}")


if __name__ == "__main__":
    main()
