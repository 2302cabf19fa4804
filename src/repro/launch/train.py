"""Training launcher.

    python -m repro.launch.train --arch olmo-1b --smoke --steps 20

``--smoke`` uses the reduced same-family config on the local device
mesh; full configs are intended for real pods (or the dry-run).
"""
from __future__ import annotations

import argparse

import jax

import repro.configs as C
from repro.runtime.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=C.ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", type=str, default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches")
    args = ap.parse_args()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    tcfg = TrainerConfig(total_steps=args.steps,
                         checkpoint_every=args.ckpt_every,
                         checkpoint_dir=args.ckpt_dir,
                         global_batch=args.batch, seq_len=args.seq,
                         accum_steps=args.accum)
    mesh = jax.make_mesh((args.dp, args.tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    trainer = Trainer(cfg, tcfg, mesh=mesh)
    state = trainer.run_with_recovery()
    print(f"finished at step {state.step}")
    for rec in trainer.metrics_log[-5:]:
        print(rec)


if __name__ == "__main__":
    main()
