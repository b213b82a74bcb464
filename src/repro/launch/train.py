"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

Runs the fault-tolerant training loop (train/loop.py) for any registered
architecture. `--smoke` selects the reduced config (CPU-runnable); the full
configs are for real accelerator meshes — their distribution plan is
validated by `repro.launch.dryrun`.

On a multi-host cluster this same entry point is started once per host
(jax.distributed.initialize picks up the coordinator from the environment);
the data pipeline shards by process index and the checkpoint manager writes
per-host shards.
"""
from __future__ import annotations

import argparse
import dataclasses

from repro import configs
from repro.launch import compile_cache
from repro.optim import adamw
from repro.train import loop as loop_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--act-impl", default="cordic_fixed")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)
    compile_cache.setup()

    cfg = (configs.get_smoke(args.arch, act_impl=args.act_impl) if args.smoke
           else configs.get_config(args.arch, act_impl=args.act_impl))
    if cfg.input_mode != "tokens":
        cfg = dataclasses.replace(cfg, input_mode="tokens")
    print(f"[train] arch={cfg.name} params={cfg.param_counts()['total'] / 1e6:.1f}M "
          f"act={cfg.act_impl} compress={args.compress}")

    lc = loop_lib.LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                             ckpt_dir=args.ckpt_dir, accum=args.accum,
                             compress=args.compress)
    out = loop_lib.run(cfg, lc, opt_cfg=adamw.AdamWConfig(lr=args.lr))
    print(f"[train] final loss {out['final_loss']:.4f} after "
          f"{len(out['history'])} steps; restarts={out['restarts']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
