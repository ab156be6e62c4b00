"""Training driver.

CPU-scale smoke:   PYTHONPATH=src python -m repro.launch.train \
                       --arch qwen1.5-4b --scale tiny --steps 30
Production shapes lower through the same code path as the dry-run; on a
real pod remove ``--scale tiny`` and launch one process per host with
``jax.distributed.initialize`` (the batch plane's job script does this).
"""
from __future__ import annotations

import argparse
import shutil

from repro.configs import get_config, scaled_down
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.training.optimizer import OptConfig
from repro.training.schedule import SCHEDULES
from repro.training.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="warmup_cosine",
                    choices=sorted(SCHEDULES))
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "sgd"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--metrics-out", default="",
                    help="write a Prometheus text snapshot (step-time "
                         "histogram, tokens/s, est. MFU) here")
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto trace_event JSON of the "
                         "step/checkpoint/failure timeline here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.scale == "tiny":
        cfg = scaled_down(cfg)
    if args.fresh:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    import functools
    sched = functools.partial(
        SCHEDULES[args.schedule], peak_lr=args.lr,
        warmup_steps=max(args.steps // 10, 1), total_steps=args.steps)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq_len,
                                  global_batch=args.global_batch))
    obs = None
    if args.metrics_out or args.trace_out:
        from repro.obs import Observability
        obs = Observability()
    tr = Trainer(cfg, OptConfig(name=args.optimizer, lr=args.lr), data,
                 TrainerConfig(num_steps=args.steps,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir,
                               log_every=max(args.steps // 10, 1)),
                 schedule_fn=sched, obs=obs)
    if tr.restore_latest():
        print(f"resumed from checkpoint at step {tr.step}")
    print(f"training {cfg.name} ({cfg.param_count():,} params) "
          f"for {args.steps} steps")
    res = tr.run()
    for m in res["log"]:
        print(f"  step {m['step']:5d} loss {m['loss']:.4f} "
              f"acc {m['accuracy']:.3f} lr {m['lr']:.2e} "
              f"gnorm {m['grad_norm']:.2f}")
    print(f"done: final_step={res['final_step']} "
          f"restarts={res['restarts']}")
    if obs is not None:
        if args.metrics_out:
            obs.write_metrics(args.metrics_out)
            print(f"metrics snapshot -> {args.metrics_out}")
        if args.trace_out:
            obs.write_trace(args.trace_out)
            print(f"perfetto trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
