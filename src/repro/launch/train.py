"""Training launcher.

Two modes:

* default: reduced config of the selected arch, runs real steps
  through the fault-tolerant Trainer.
* ``--production``: a compile-only rehearsal — builds the full-size
  bundle against the 16×16 production mesh on 512 placeholder CPU host
  devices and lowers + compiles it (the dry run); nothing executes on
  a chip.

Examples::

    python -m repro.launch.train --arch llama3_8b --steps 50
    python -m repro.launch.train --arch mixtral_8x7b --production \
        --shape train_4k
"""
from __future__ import annotations

import argparse
import sys

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.runtime import FailureInjector, Trainer, TrainerConfig

from .compile_cache import enable_compile_cache


def main(argv=None) -> int:
    from repro.obs import setup_logging
    _log = setup_logging()  # CLI entry point: bare messages on stdout
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--production", action="store_true",
                    help="compile-only rehearsal of the full-size train "
                         "step on CPU host devices (the dry run)")
    ap.add_argument("--inject-fault-at", type=int, default=None)
    args = ap.parse_args(argv)

    if args.production:
        from repro.launch.dryrun import run_cell, use_host_devices
        use_host_devices()
        result = run_cell(args.arch, args.shape, multi_pod=False)
        return 0 if result["status"] == "ok" else 1

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    shape = ShapeConfig("smoke_train", args.seq_len, args.batch, "train")
    injector = None
    if args.inject_fault_at is not None:
        injector = FailureInjector(fail_at_steps=(args.inject_fault_at,))
    trainer = Trainer(
        cfg, shape,
        TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir),
        attn_chunk=16,
        injector=injector,
    )
    hist = trainer.run()
    _log.info("steps: %d  first loss: %.4f  last loss: %.4f",
              len(hist["loss"]), hist["loss"][0], hist["loss"][-1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
