"""Serving launcher: continuous batching over the graph-native executors.

The default path runs the Ripple serving stack end to end — prefill and
batched greedy decode are Ripple graphs (``launch/steps.py``), the KV
cache is a layout-polymorphic RecordArray state tensor, and the
continuous-batching front end (``runtime/batcher.py``) admits requests
into the decode executor's fixed batch slots.  Encoder-decoder and VLM
archs fall back to the legacy jit loop automatically.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --batch 4 --prompt-len 16 --gen 16

``--smoke`` hard-asserts the PR-6 acceptance criteria: the graph-native
argmax token sequences are identical to the legacy jit path, the steady
decode loop traced exactly once, and a freshly constructed worker
(new Batcher + Executors from the same cfg/params) serves with ZERO new
traces, straight from the process-wide executable cache.

``--chaos`` (with ``--smoke``) re-serves the same prompts under a
deterministic fault schedule (``repro.runtime.faults``): mid-decode
step failures, an admission-scatter failure, and a device-region fault
inside the decode executor — asserting the Batcher's request-log
replay recovers with argmax-identical token streams and that a fresh
worker afterwards still serves with zero new traces.
"""

from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.compile_cache import enable_compile_cache
from repro.launch import steps as S
from repro.models.lm import init_lm


class LegacyRun(NamedTuple):
    """What :func:`legacy_generate` produced."""

    tokens: np.ndarray          # (B, gen) greedy tokens
    gaps: np.ndarray            # (B, gen) top-2 logit gap behind each token
    prefill_logits: jax.Array   # (B, V) logits after the prompt
    t_prefill: float
    t_decode: float


def _top2_gap(logits):
    top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
    return np.asarray(top2[..., 0] - top2[..., 1])


def legacy_generate(cfg, params, batch, gen: int, max_seq: int) -> LegacyRun:
    """The pre-Ripple jit loop: prefill + uniform batched greedy decode."""
    from repro.models.blocks import ShardCtx
    from repro.models.lm import prefill as prefill_raw

    decode_fn = jax.jit(S.make_decode_step(cfg, None), donate_argnums=1)
    t0 = time.perf_counter()
    logits, caches = jax.jit(
        lambda p, b: prefill_raw(p, b, cfg, ShardCtx(), max_seq=max_seq)
    )(params, batch)
    prefill_logits = logits
    t_prefill = time.perf_counter() - t0
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out_tokens, gaps = [np.asarray(toks)], [_top2_gap(logits)]
    t1 = time.perf_counter()
    for _ in range(gen - 1):
        logits, caches = decode_fn(params, caches, toks)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(toks))
        gaps.append(_top2_gap(logits))
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t1
    return LegacyRun(np.stack(out_tokens, axis=1), np.stack(gaps, axis=1),
                     prefill_logits, t_prefill, t_decode)


def serve_legacy(cfg, params, args):
    rng = np.random.default_rng(0)
    B = args.batch
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (B, args.prompt_len)).astype(np.int32))}
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(rng.standard_normal(
            (B, S.ENC_LEN_SERVE, cfg.frontend_dim)).astype(np.float32))
    elif cfg.frontend_dim:
        batch["patches"] = jnp.asarray(rng.standard_normal(
            (B, cfg.frontend_tokens, cfg.frontend_dim)).astype(np.float32))
    max_seq = args.prompt_len + args.gen + (
        cfg.frontend_tokens if cfg.frontend_dim and not cfg.is_encdec else 0)
    gen, _, _, t_prefill, t_decode = legacy_generate(cfg, params, batch,
                                                     args.gen, max_seq)
    print(f"[serve] arch={cfg.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen} path=legacy")
    print(f"[serve] prefill {t_prefill*1e3:.0f}ms; decode "
          f"{t_decode/max(args.gen-1,1)*1e3:.1f}ms/tok "
          f"({B*(args.gen-1)/max(t_decode,1e-9):.1f} tok/s)")
    print(f"[serve] sample generations (first 3 rows):\n{gen[:3]}")
    return gen


def serve_ripple(cfg, params, args):
    from repro.runtime.batcher import Batcher

    rng = np.random.default_rng(0)
    B = args.batch
    max_seq = args.prompt_len + args.gen
    prompts = rng.integers(0, cfg.vocab_size,
                           (B, args.prompt_len)).astype(np.int32)

    t0 = time.perf_counter()
    batcher = Batcher(cfg, params, batch=B, max_seq=max_seq)
    reqs = [batcher.submit(p, max_new_tokens=args.gen) for p in prompts]
    batcher.run()
    t_total = time.perf_counter() - t0
    gen = np.stack([r.generated for r in reqs])
    stats = batcher.cache_stats()
    n_tok = int(sum(len(r.generated) for r in reqs))
    print(f"[serve] arch={cfg.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen} path=ripple")
    print(f"[serve] {batcher.steps} decode steps, {n_tok} tokens in "
          f"{t_total*1e3:.0f}ms ({n_tok/max(t_total,1e-9):.1f} tok/s); "
          f"decode traces={stats['decode']['trace_events']}")
    print(f"[serve] sample generations (first 3 rows):\n{gen[:3]}")

    if args.smoke:
        # 1. graph-native decode == legacy jit path, token for token
        legacy = legacy_generate(
            cfg, params, {"tokens": jnp.asarray(prompts)}, args.gen,
            max_seq).tokens
        assert (gen == legacy).all(), (
            f"ripple/legacy argmax mismatch:\n{gen}\nvs\n{legacy}")
        print("[smoke] ripple == legacy argmax sequences  OK")

        # 2. the steady decode loop traced exactly once
        assert stats["decode"]["trace_events"] == 1, stats["decode"]
        print("[smoke] decode traced once across "
              f"{batcher.steps} steps  OK")

        # 3. a freshly constructed worker serves with ZERO new traces
        before = batcher.executor.cache_stats()["trace_events"]
        worker = Batcher(cfg, params, batch=B, max_seq=max_seq)
        wreqs = [worker.submit(p, max_new_tokens=args.gen)
                 for p in prompts]
        worker.run()
        wgen = np.stack([r.generated for r in wreqs])
        after = worker.executor.cache_stats()["trace_events"]
        assert worker.executor.plan.signature == \
            batcher.executor.plan.signature
        assert after == before, (
            f"fresh worker retraced: {before} -> {after}")
        assert (wgen == gen).all()
        print("[smoke] fresh worker served with 0 new traces  OK")

    if getattr(args, "chaos", False):
        gen = _chaos_smoke(cfg, params, args, prompts, gen, max_seq)
    return gen


def _chaos_smoke(cfg, params, args, prompts, want, max_seq):
    """Faulted serve smoke: re-serve the same prompts under a
    deterministic mid-decode fault schedule (decode-step failures, an
    admission failure, and a device-region fault inside the decode
    executor) and hard-assert the Batcher's request-log replay produced
    argmax-identical token streams — plus a FRESH worker after the
    chaos run still serves with zero new traces."""
    from repro.runtime.batcher import Batcher
    from repro.runtime.faults import Fault, FaultPlan, fault_scope

    plan = FaultPlan([
        Fault("batcher.step", step=2, times=2),     # two mid-decode faults
        Fault("batcher.admit", step=0),             # admission scatter fault
        Fault("executor.region", nth=8),            # inside the decode exec
    ])
    batcher = Batcher(cfg, params, batch=args.batch, max_seq=max_seq,
                      log=lambda *_: None)
    reqs = [batcher.submit(p, max_new_tokens=args.gen) for p in prompts]
    with fault_scope(plan):
        batcher.run()
    gen = np.stack([r.generated for r in reqs])
    assert plan.exhausted(), f"not every fault fired:\n{plan.report()}"
    assert batcher.failures >= 3, batcher.failures
    assert (gen == want).all(), (
        f"faulted ripple argmax mismatch:\n{gen}\nvs\n{want}")
    print(f"[chaos] {batcher.failures} injected failures recovered; "
          f"token streams identical  OK")

    # post-chaos: a fresh worker (same cfg/params) still serves from the
    # process-wide executable cache with zero new traces
    before = batcher.executor.cache_stats()["trace_events"]
    worker = Batcher(cfg, params, batch=args.batch, max_seq=max_seq)
    wreqs = [worker.submit(p, max_new_tokens=args.gen) for p in prompts]
    worker.run()
    wgen = np.stack([r.generated for r in wreqs])
    after = worker.executor.cache_stats()["trace_events"]
    assert after == before, f"post-chaos worker retraced: {before}->{after}"
    assert (wgen == want).all()
    print("[chaos] fresh worker after chaos: 0 new traces  OK")
    return gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--legacy", action="store_true",
                    help="force the pre-Ripple jit loop")
    ap.add_argument("--chaos", action="store_true",
                    help="re-serve under a deterministic fault schedule "
                         "and assert replay-log recovery (ripple path)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    params, _ = init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    if args.legacy or cfg.is_encdec or cfg.frontend_dim:
        return serve_legacy(cfg, params, args)
    return serve_ripple(cfg, params, args)


if __name__ == "__main__":
    main()
