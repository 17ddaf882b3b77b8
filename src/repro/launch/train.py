"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On a CPU host it trains the arch's reduced smoke config end-to-end; on a
TPU slice the same entry point runs the full config (--full).
"""
from __future__ import annotations

import argparse
import dataclasses

from repro import optim
from repro.configs.registry import all_arch_ids, get
from repro.models.config import Runtime
from repro.training import TrainConfig, train
from repro.utils import logger


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=all_arch_ids())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_train")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="use the full (paper) config instead of smoke")
    ap.add_argument("--int8-opt", action="store_true")
    args = ap.parse_args()

    spec = get(args.arch)
    cfg = spec.config if args.full else spec.smoke
    rt = Runtime(remat=False, xent_chunk=32, moe_groups=1,
                 mamba_chunk=16, mlstm_chunk=16)
    from repro.data import DataConfig
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    ocfg = optim.AdamWConfig(
        lr=args.lr, state_dtype="int8" if args.int8_opt else "float32")
    res = train(cfg, rt, TrainConfig(
        steps=args.steps, checkpoint_dir=f"{args.checkpoint_dir}/{cfg.name}",
        checkpoint_every=args.checkpoint_every), ocfg, data=data)
    logger.info("done: %d steps, loss %.4f -> %.4f, %d stragglers, resumed@%d",
                len(res.losses), res.losses[0] if res.losses else float("nan"),
                res.losses[-1] if res.losses else float("nan"),
                res.stragglers, res.resumed_from)


if __name__ == "__main__":
    main()
