"""Continuous-batching LM serving demo on the Ripple executor.

Requests with ragged prompt lengths and per-request EOS stream through
``runtime.Batcher``: prefill and batched greedy decode are Ripple graphs
(one node per layer), the KV cache is a layout-polymorphic RecordArray
state tensor whose storage the layout solver picks, and retired slots are
immediately re-filled from the queue — more requests than batch slots is
the normal case, not an error.  Encoder-decoder / VLM archs fall back to
the legacy jit loop (see repro/launch/serve.py).

  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-12b --smoke
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))

import jax
import numpy as np

import repro.configs as configs
from repro.compile_cache import enable_compile_cache
from repro.models.lm import init_lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch slots (requests = 2x this)")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-gen", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch)
    params, _ = init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    if cfg.is_encdec or cfg.frontend_dim:
        print(f"[serve_lm] {cfg.name} is encoder-decoder/VLM; use "
              f"`python -m repro.launch.serve --legacy` for this arch")
        return

    from repro.runtime import Batcher

    rng = np.random.default_rng(0)
    eos = 0  # token 0 acts as EOS for the demo
    n_req = 2 * args.batch
    max_seq = args.prompt_len + args.max_gen

    batcher = Batcher(cfg, params, batch=args.batch, max_seq=max_seq,
                      eos_token=eos)
    t0 = time.perf_counter()
    reqs = []
    for i in range(n_req):
        # ragged prompts: lengths vary per request
        L = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab_size, (L,)).astype(np.int32)
        reqs.append(batcher.submit(prompt, max_new_tokens=args.max_gen))
    batcher.run()
    dt = time.perf_counter() - t0

    n_tok = sum(len(r.generated) for r in reqs)
    lens = [len(r.generated) for r in reqs]
    stats = batcher.cache_stats()["decode"]
    print(f"[serve_lm] arch={cfg.name} slots={args.batch} "
          f"requests={n_req} max_gen={args.max_gen}")
    print(f"[serve_lm] {batcher.steps} decode steps, {n_tok} tokens in "
          f"{dt*1e3:.0f} ms ({n_tok/max(dt,1e-9):.1f} tok/s); "
          f"decode traces={stats['trace_events']}; "
          f"request lengths {lens}")
    for r in reqs[:3]:
        print(f"  req{r.rid} (prompt {len(r.prompt)}): "
              f"{r.generated[:12]}{'...' if len(r.generated) > 12 else ''}")


if __name__ == "__main__":
    main()
