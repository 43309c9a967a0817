"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Uses the production trainer (sharded step, prefetching pipeline, async
checkpoints, restart-exact resume).  The default config is a ~100M-parameter
dense transformer (qwen3-family blocks); on this CPU container the default
invocation trims steps — pass ``--steps 300`` on real hardware.

    PYTHONPATH=src python examples/train_lm.py --steps 300
"""

import argparse

from repro.configs import get_arch
from repro.launch.train import train as _train
import repro.launch.train as train_mod
from repro.configs.base import ArchConfig
from repro.utils.compile_cache import enable_compile_cache

# ~100M params: 12 × (d512 swiglu-2048 blocks, 8 heads) + 32k vocab embed/head
LM100M = ArchConfig(
    name="lm-100m", family="dense",
    n_layers=12, d_model=512, n_heads=8, n_kv_heads=4,
    d_ff=2048, vocab=32000, head_dim=64, qk_norm=True,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/step_jax_lm100m")
    args = ap.parse_args()

    # register the 100M config under the trainer's lookup
    import repro.configs as C
    C.ARCHS[LM100M.name] = LM100M

    losses = _train(LM100M.name, smoke=False, steps=args.steps, batch=args.batch,
                    seq=args.seq, ckpt_dir=args.ckpt_dir, ckpt_every=25)
    print(f"[train_lm] {LM100M.name}: loss {losses[0]:.3f} → {losses[-1]:.3f} "
          f"over {len(losses)} steps (resume-capable via {args.ckpt_dir})")


if __name__ == "__main__":
    main()
