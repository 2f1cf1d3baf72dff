"""End-to-end LM training driver: data pipeline -> sharded train step ->
fault-tolerant supervisor with async checkpointing.

Default: a ~12M-param qwen3-family model for 200 steps (CPU-feasible,
~5 min).  ``--big`` trains a ~100M-param model (same code path; budget
accordingly on CPU).  On TPU hardware the same driver scales to the
production mesh via --mesh.

  PYTHONPATH=src python examples/train_lm.py --steps 200
  PYTHONPATH=src python examples/train_lm.py --big --steps 300
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.data import SyntheticLM
from repro.launch.steps import make_train_step
from repro.models.lm import init_lm, param_count
from repro.optim import cosine_schedule
from repro.runtime import Supervisor


def model_config(big: bool):
    base = configs.get("qwen3-8b")  # family: GQA + qk-norm + swiglu
    if big:
        return base.with_(n_layers=12, d_model=512, n_heads=8, n_kv_heads=4,
                          head_dim=64, d_ff=2048, vocab_size=32000,
                          param_dtype="float32", compute_dtype="float32",
                          attn_impl="tri", q_chunk=128, k_chunk=128,
                          remat="none")
    return base.with_(n_layers=8, d_model=256, n_heads=8, n_kv_heads=4,
                      head_dim=32, d_ff=1024, vocab_size=8192,
                      param_dtype="float32", compute_dtype="float32",
                      attn_impl="tri", q_chunk=128, k_chunk=128,
                      remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--big", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = model_config(args.big)
    print(f"[train_lm] params: {param_count(cfg):,} "
          f"({'~100M' if args.big else '~12M'} config)")

    step_fn, opt = make_train_step(
        cfg, None, lr=cosine_schedule(3e-4, 20, args.steps))
    params, _ = init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    jstep = jax.jit(step_fn, donate_argnums=0)

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch)
    losses = []

    def wrapped(state, batch):
        state, m = jstep(state, batch)
        losses.append(float(m["loss"]))
        return state

    def batch_at(i):
        return {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}

    sup = Supervisor(step_fn=wrapped, ckpt=CheckpointManager(args.ckpt_dir),
                     ckpt_every=100)
    state = sup.run(state, batch_at, start_step=0, num_steps=args.steps,
                    on_step=lambda s, _: print(
                        f"step {s:4d}  loss {losses[-1]:.4f}  "
                        f"({sup.stats.last*1e3:.0f} ms)")
                    if s % 20 == 0 else None)
    print(f"[train_lm] loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
          f"{args.steps} steps; final ppl ~ {2.718 ** losses[-1]:.1f}")
    assert losses[-1] < losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
