"""End-to-end training driver: a ~100M-parameter llama-style model trained for
a few hundred steps on the synthetic Markov-chain pipeline, with checkpointing,
an injected mid-run fault (restart exercised for real), and loss reporting.

    PYTHONPATH=src python examples/train_100m.py --steps 300
"""

import argparse
import shutil
import tempfile

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticSource
from repro.jax_cache import enable_compile_cache
from repro.launch import steps as S
from repro.optim import adamw
from repro.runtime.fault_tolerance import ResilientLoop

# ~100M params: 12 layers x d_model 768, llama-style GQA + SwiGLU.
CFG_100M = ModelConfig(
    name="repro-100m",
    family="dense",
    num_layers=12,
    d_model=768,
    num_heads=12,
    num_kv_heads=4,
    d_ff=2048,
    vocab_size=32768,
    remat="none",
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--inject-fault", type=int, default=150,
                    help="step at which to inject a fault (-1 to disable)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = CFG_100M
    shape = ShapeConfig("train100m", args.seq, args.batch, "train")
    opt_cfg = adamw.AdamWConfig(lr=6e-4, warmup_steps=30,
                                total_steps=args.steps)
    model, train_step = S.make_train_step(cfg, opt_cfg)
    jstep = jax.jit(train_step, donate_argnums=(0,))
    state = S.init_train_state(model, cfg, opt_cfg, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"model: {n/1e6:.1f}M params | batch {args.batch}x{args.seq} "
          f"| {args.steps} steps")

    source = SyntheticSource(cfg, shape, DataConfig(seed=0))
    ckpt_dir = tempfile.mkdtemp(prefix="repro100m_")

    losses = []

    def step_fn(state, batch):
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = jstep(state, jb)
        return state, {k: float(v) for k, v in metrics.items()}

    def log(m):
        if "loss" in m:
            losses.append(m["loss"])
            if m["step"] % 25 == 0:
                print(f"step {m['step']:4d}  loss {m['loss']:.4f}  "
                      f"gnorm {m['grad_norm']:.2f}  {m['dt']*1e3:.0f} ms")
        else:
            print(f"*** {m}")

    loop = ResilientLoop(step_fn, source, ckpt_dir, save_every=50)
    faults = {args.inject_fault} if args.inject_fault >= 0 else None
    state, step, _, monitor = loop.run(state, 0, args.steps,
                                       fault_schedule=faults, log=log)
    first = sum(losses[:10]) / 10
    last = sum(losses[-10:]) / 10
    print(f"\ndone: loss {first:.3f} -> {last:.3f} "
          f"({'LEARNING' if last < first - 0.5 else 'check hyperparams'}) | "
          f"restarts survived, stragglers flagged: {monitor.flagged}")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    assert last < first, "training must reduce loss"


if __name__ == "__main__":
    main()
