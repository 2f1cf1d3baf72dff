"""Training launcher: data pipeline -> sharded train step -> supervisor
(checkpoint/restart, straggler stats) -> metrics.

On real hardware this runs under ``jax.distributed.initialize`` with the
production mesh; on this container it runs reduced configs on CPU (the
end-to-end driver for examples/train_lm.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 100 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.data import SyntheticLM
from repro.launch import steps as S
from repro.launch.mesh import make_mesh
from repro.models.lm import init_lm, param_count
from repro.optim import cosine_schedule
from repro.runtime import Supervisor


def build_trainer(cfg, mesh, *, total_steps: int, peak_lr: float = 3e-4):
    step_fn, opt = S.make_train_step(
        cfg, mesh, lr=cosine_schedule(peak_lr, min(100, total_steps // 10),
                                      total_steps))
    tp = 1 if mesh is None else mesh.shape.get("model", 1)
    params, _ = init_lm(cfg, jax.random.PRNGKey(0), tp=tp)
    if mesh is not None:
        p_sds, _ = S.param_specs(cfg, mesh)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s.sharding),
                              params, p_sds)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    jstep = jax.jit(step_fn, donate_argnums=0)
    return jstep, state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="",
                    help="e.g. '2x2:data,model' (default: no mesh)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh = None
    if args.mesh:
        shape_s, axes_s = args.mesh.split(":")
        mesh = make_mesh(tuple(int(x) for x in shape_s.split("x")),
                         tuple(axes_s.split(",")))

    print(f"[train] arch={cfg.name} params={param_count(cfg):,} "
          f"steps={args.steps} batch={args.batch}x{args.seq}")
    jstep, state = build_trainer(cfg, mesh, total_steps=args.steps)

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch)
    metrics_log = []

    def step_and_log(state, batch):
        state, m = jstep(state, batch)
        metrics_log.append({k: float(v) for k, v in m.items()})
        return state

    def batch_at(i):
        b = data.batch_at(i)
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.is_encdec:
            out["frames"] = jnp.asarray(np.random.default_rng(i)
                                        .standard_normal(
                (args.batch, args.seq, cfg.frontend_dim)).astype(np.float32))
        elif cfg.frontend_dim:
            out["patches"] = jnp.asarray(np.random.default_rng(i)
                                         .standard_normal(
                (args.batch, cfg.frontend_tokens, cfg.frontend_dim))
                .astype(np.float32))
        return out

    sup = Supervisor(step_fn=step_and_log,
                     ckpt=CheckpointManager(args.ckpt_dir),
                     ckpt_every=args.ckpt_every)
    t0 = time.time()
    state = sup.run(state, batch_at, start_step=0, num_steps=args.steps,
                    on_step=lambda s, _: (
                        print(f"[train] step {s}: "
                              f"loss={metrics_log[-1]['loss']:.4f} "
                              f"gnorm={metrics_log[-1]['grad_norm']:.3f} "
                              f"{sup.stats.last*1e3:.0f}ms")
                        if s % args.log_every == 0 else None))
    dt = time.time() - t0
    print(f"[train] done: {args.steps} steps in {dt:.1f}s; "
          f"loss {metrics_log[0]['loss']:.4f} -> {metrics_log[-1]['loss']:.4f}; "
          f"stragglers={len(sup.stats.stragglers)}")
    return metrics_log


if __name__ == "__main__":
    main()
