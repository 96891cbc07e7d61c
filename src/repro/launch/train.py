"""Training launcher with bounded-restart supervision.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Production posture: XLA latency-hiding-scheduler flags are preset (compute/
communication overlap on real TPU); the supervisor restarts the trainer from
its last checkpoint on retryable failures; SIGTERM checkpoints and exits.
"""
import os

# compute/comm overlap: async collectives + latency-hiding scheduler.
_PERF_FLAGS = (
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    " --xla_tpu_overlap_compute_collective_tc=true"
)
if os.environ.get("REPRO_TPU_PERF_FLAGS", "0") == "1":
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + _PERF_FLAGS

import argparse

from repro.configs import get_config
from repro.data import DataConfig
from repro.dist.fault_tolerance import run_with_restarts
from repro.launch.compile_cache import use_compile_cache
from repro.optim import AdamWConfig
from repro.train import TrainConfig, Trainer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--no-int8-state", action="store_true")
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    tc = TrainConfig(
        total_steps=args.steps,
        microbatches=args.microbatches,
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt_dir,
        grad_compression=args.grad_compression,
    )
    opt = AdamWConfig(
        lr=args.lr, warmup_steps=max(args.steps // 20, 5),
        total_steps=args.steps, int8_state=not args.no_int8_state,
    )
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)

    def attempt(i: int):
        print(f"[supervisor] attempt {i}")
        trainer = Trainer(cfg, opt, tc, dc, install_signals=True)
        trainer.run()

    run_with_restarts(attempt, max_restarts=args.max_restarts)
    print("[supervisor] training complete")


if __name__ == "__main__":
    main()
