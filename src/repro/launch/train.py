"""Training launcher: ``--arch <id>`` selects any assigned architecture.

On this CPU container use ``--reduce`` (family-preserving reduced config);
at scale drop it and pass ``--mesh pod1|pod2``.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduce \
      --steps 50 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import all_archs, get_config, reduce_config
from repro.data.pipeline import DataConfig
from repro.distributed.sharding import use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import init_params, param_count
from repro.train import StepOptions, init_train_state
from repro.train.loop import LoopConfig, run
from repro.train.optim import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=all_archs())
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress-bits", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "pod1", "pod2"])
    ap.add_argument("--rules", default="fsdp_sp")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    print(f"arch={cfg.name} params={param_count(cfg)/1e6:.1f}M "
          f"blocks={cfg.n_blocks()}")

    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    loop = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=5)
    opts = StepOptions(microbatches=args.microbatches,
                       grad_compress_bits=args.grad_compress_bits)

    def init_fn():
        return init_train_state(
            init_params(cfg, jax.random.PRNGKey(0)), opts)

    opt = AdamWConfig(lr_peak=args.lr, warmup_steps=max(2, args.steps // 10),
                      total_steps=args.steps)
    if args.mesh == "none":
        run(cfg, loop, data, init_fn, opt, opts)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "pod2")
        with use_rules(mesh, args.rules):
            run(cfg, loop, data, init_fn, opt, opts)


if __name__ == "__main__":
    main()
