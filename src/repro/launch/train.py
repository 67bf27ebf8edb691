"""Production training launcher.

    python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 100 --batch 8 --seq 128 [--mesh 2,2,2] [--act-mode seq_tp]

On this container only smoke configs are trainable for real; the full
configs train through the identical code path on a pod (the mesh flag
accepts any shape whose product equals the device count).  Checkpoints,
fault-tolerant restart, activation monitoring and the sharded data
pipeline are all on by default — this is the entry point a cluster job
would exec per host.
"""
import os
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor"))
    ap.add_argument("--mesh", default="",
                    help="comma shape, e.g. 2,2,2 -> (pod,data,model); "
                         "empty = single device")
    ap.add_argument("--act-mode", default="seq_tp",
                    choices=("embed_tp", "seq_tp", "dp_only"))
    ap.add_argument("--host-devices", type=int, default=0,
                    help="fake host devices (testing the mesh path on CPU)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--monitor", action="store_true",
                    help="SnS activation monitor")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    import jax
    from repro.configs import get_config
    from repro.data import zipf_token_stream
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_mesh
    from repro.train.steps import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        names = ("pod", "data", "model")[-len(shape):]
        mesh = make_mesh(shape, names)
        dp = tuple(a for a in names if a != "model")
        shlib.set_activation_sharding(mesh, dp, "model",
                                      act_mode=args.act_mode)
        print(f"[mesh] {dict(mesh.shape)} act_mode={args.act_mode}")

    tcfg = TrainStepConfig(optimizer=args.optimizer, peak_lr=args.lr,
                           warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps,
                           q_chunk=min(1024, args.seq))
    rc = TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir, log_every=10,
                       monitor_activations=args.monitor)

    def batch_fn(step):
        return zipf_token_stream(jax.random.key(step), args.batch,
                                 args.seq, cfg.vocab_size)

    tr = Trainer(cfg, tcfg, rc, batch_fn)
    if tr.start_step:
        print(f"[resume] from step {tr.start_step}")
    out = tr.run()
    for m in out["metrics"]:
        print(f"  step {int(m['step']):5d} loss {m['loss']:.4f} "
              f"lr {m['lr']:.2e} gnorm {m['grad_norm']:.2f}")
    print(f"[done] {out['final_step']} steps in {out['wall_s']:.1f}s; "
          f"checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
