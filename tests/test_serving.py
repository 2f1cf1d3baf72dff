"""Graph-native serving stack: decode/prefill Ripple graphs, the
continuous-batching front end, and the zero-trace worker pattern.

Ground truth throughout is the legacy jit loop (``models.lm.prefill`` +
``decode_step``) — greedy decode is deterministic, so every comparison
is exact token equality, not closeness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.core.layout import Layout
from repro.launch import steps
from repro.models import lm
from repro.runtime.batcher import Batcher
from repro.runtime.supervisor import TransientError

MAX_SEQ = 20


@pytest.fixture(scope="module")
def served():
    cfg = configs.get_smoke("qwen3_8b")
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    ctx = steps.make_ctx(cfg, None)

    def legacy(prompt, n):
        """Per-request greedy chain through the legacy jit path."""
        logits, caches = jax.jit(
            lambda p, b: lm.prefill(p, b, cfg, ctx, max_seq=MAX_SEQ)
        )(params, {"tokens": jnp.asarray(prompt)[None]})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out = [int(tok[0])]
        dstep = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, cfg, ctx))
        for _ in range(n - 1):
            lg, caches = dstep(params, caches, tok)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            out.append(int(tok[0]))
        return out

    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (3, 5, 3, 5)]
    want_n = [4, 3, 4, 2]
    refs = [legacy(p, n) for p, n in zip(prompts, want_n)]
    return cfg, params, prompts, want_n, refs, legacy


def test_batcher_matches_legacy_chains(served):
    """More requests than slots, ragged prompt lengths: every request's
    graph-native greedy chain is argmax-identical to its legacy chain,
    and the steady decode loop traced exactly once."""
    cfg, params, prompts, want_n, refs, _ = served
    b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ)
    reqs = [b.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, want_n)]
    done = b.run()
    assert len(done) == len(reqs)
    for req, ref in zip(reqs, refs):
        assert req.status == "done"
        assert req.generated == ref, (req.rid, req.generated, ref)
    assert b.cache_stats()["decode"]["trace_events"] == 1
    # latency bookkeeping: one timestamp per generated token
    assert all(len(r.token_times) == len(r.generated) for r in reqs)


def test_fresh_worker_serves_with_zero_traces(served):
    """A re-instantiated Batcher over the SAME cfg/params objects gets an
    identical plan signature and serves from the process-wide executable
    cache — zero new traces."""
    cfg, params, prompts, want_n, refs, _ = served
    a = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ)
    for p, n in zip(prompts[:2], want_n[:2]):
        a.submit(p, max_new_tokens=n)
    a.run()
    before = a.executor.cache_stats()["trace_events"]

    w = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ)
    reqs = [w.submit(p, max_new_tokens=n)
            for p, n in zip(prompts[:2], want_n[:2])]
    w.run()
    assert w.executor.plan.signature == a.executor.plan.signature
    assert w.executor.cache_stats()["trace_events"] == before
    for req, ref in zip(reqs, refs[:2]):
        assert req.generated == ref


def test_aosoa_decode_plan_identical_tokens(served):
    """Force the decode plan's KV storage to AoSoA (the layout PR-6
    lifted): the vector-pos token writes and the admission scatter run
    through the tiled layout and the tokens stay argmax-identical."""
    cfg, params, prompts, want_n, refs, _ = served
    slots = steps.serving_cache_slots(cfg, 2, MAX_SEQ)
    overrides = {s.tensors[0].name: Layout.AOSOA
                 for s in slots if s.kind in ("A", "L")}
    assert overrides, "qwen3 smoke cfg must have attention layers"
    b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ,
                executor_opts={"layout_overrides": overrides})
    for name, lay in overrides.items():
        assert b.executor.plan.initial[name] is Layout.AOSOA
    reqs = [b.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, want_n)]
    b.run()
    for req, ref in zip(reqs, refs):
        assert req.generated == ref, (req.rid, req.generated, ref)


def test_prefill_ahead_identical_tokens_and_consumed(served):
    """Admission overlap: prefills computed behind the dispatched decode
    step are cached per-request and consumed at admission — tokens are
    identical to the no-prefill-ahead path, and nothing leaks."""
    cfg, params, prompts, want_n, refs, _ = served
    for ahead in (False, True):
        b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ,
                    prefill_ahead=ahead)
        reqs = [b.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, want_n)]
        done = b.run()
        assert len(done) == len(reqs)
        for req, ref in zip(reqs, refs):
            assert req.generated == ref, (ahead, req.rid)
        assert b._prepared == {}     # every prepared prefill was consumed


def test_prefill_ahead_never_reused_after_replay(served):
    """Recovery safety: a request replayed after a TransientError has
    generated tokens — its cached fresh-prompt prefill must NOT be
    reused (the replay re-prefills prompt + generated)."""
    cfg, params, prompts, want_n, refs, _ = served
    boom = {"at": 2}

    def hook(step):
        if step == boom["at"]:
            boom["at"] = -1
            raise TransientError("injected")

    b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ, step_hook=hook,
                prefill_ahead=True, log=lambda *_: None)
    reqs = [b.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, want_n)]
    b.run()
    assert b.failures == 1
    for req, ref in zip(reqs, refs):
        assert req.status == "done"
        assert req.generated == ref, (req.rid, req.generated, ref)


def test_eviction_from_queue_and_live_slot(served):
    cfg, params, prompts, _, _, legacy = served
    b = Batcher(cfg, params, batch=1, max_seq=MAX_SEQ)
    r1 = b.submit(prompts[0], max_new_tokens=6)
    r2 = b.submit(prompts[1], max_new_tokens=3)
    b.step()
    assert b.evict(r2.rid) and r2.status == "evicted"   # still queued
    assert b.evict(r1.rid) and r1.status == "evicted"   # live slot
    assert b.evict(999) is False
    r3 = b.submit(prompts[2], max_new_tokens=3)
    b.run()
    assert r3.status == "done"
    assert r3.generated == legacy(prompts[2], 3)


def test_eos_retirement(served):
    cfg, params, prompts, _, refs, _ = served
    eos = refs[0][1]                    # second token of request 0
    b = Batcher(cfg, params, batch=1, max_seq=MAX_SEQ, eos_token=eos)
    r = b.submit(prompts[0], max_new_tokens=10)
    b.run()
    assert r.status == "done"
    assert r.generated == refs[0][:2] and r.generated[-1] == eos


def test_transient_failure_replays_request_log(served):
    """A TransientError mid-decode: the batcher re-prefills every
    in-flight request from its request log (prompt + generated) and the
    final chains are still exact — the log IS the checkpoint."""
    cfg, params, prompts, want_n, refs, _ = served
    boom = {"at": 2}

    def hook(step):
        if step == boom["at"]:
            boom["at"] = -1
            raise TransientError("injected")

    b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ, step_hook=hook,
                log=lambda *_: None)
    reqs = [b.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, want_n)]
    b.run()
    assert b.failures == 1
    for req, ref in zip(reqs, refs):
        assert req.status == "done"
        assert req.generated == ref, (req.rid, req.generated, ref)


def test_injected_admission_and_step_faults_replay_identically(served):
    """Scheduled faults (repro.runtime.faults) at the batcher's real
    injection sites — an admission scatter failure and a mid-decode step
    failure — recover through the request-log replay with chains exactly
    equal to the fault-free references."""
    from repro.runtime.faults import Fault, FaultPlan, RetryPolicy, fault_scope

    cfg, params, prompts, want_n, refs, _ = served
    b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ, log=lambda *_: None,
                retry=RetryPolicy(base_delay=0.0, sleep=lambda d: None))
    reqs = [b.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, want_n)]
    plan = FaultPlan([Fault("batcher.admit", step=0),
                      Fault("batcher.step", step=1)])
    with fault_scope(plan):
        b.run()
    assert plan.exhausted(), plan.report()
    assert b.failures == 2
    for req, ref in zip(reqs, refs):
        assert req.status == "done"
        assert req.generated == ref, (req.rid, req.generated, ref)


def test_fault_during_recovery_loses_no_requests(served):
    """Recovery itself takes a fault: the decode step fails at step 2 and
    the replay's re-admission fails too.  The second recovery attempt
    must still see every live request (slots are never cleared
    destructively) and finish all chains exactly."""
    from repro.runtime.faults import Fault, FaultPlan, RetryPolicy, fault_scope

    cfg, params, prompts, want_n, refs, _ = served
    b = Batcher(cfg, params, batch=2, max_seq=MAX_SEQ, log=lambda *_: None,
                retry=RetryPolicy(base_delay=0.0, sleep=lambda d: None))
    reqs = [b.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, want_n)]
    plan = FaultPlan([Fault("batcher.step", step=2),
                      Fault("batcher.admit", step=2)])   # fires mid-replay
    with fault_scope(plan):
        b.run()
    assert plan.exhausted(), plan.report()
    assert b.failures == 2
    for req, ref in zip(reqs, refs):
        assert req.status == "done", (req.rid, req.status)
        assert req.generated == ref, (req.rid, req.generated, ref)


def test_failure_budget_exhausted_raises(served):
    cfg, params, prompts, _, _, _ = served

    def hook(step):
        raise TransientError("always")

    b = Batcher(cfg, params, batch=1, max_seq=MAX_SEQ, step_hook=hook,
                max_retries_per_step=2, log=lambda *_: None)
    b.submit(prompts[0], max_new_tokens=4)
    with pytest.raises(RuntimeError, match="failed"):
        b.run()


def test_submit_validation(served):
    cfg, params, _, _, _, _ = served
    b = Batcher(cfg, params, batch=1, max_seq=MAX_SEQ)
    with pytest.raises(ValueError, match="empty"):
        b.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_seq"):
        b.submit(np.ones((MAX_SEQ,), np.int32))


def test_state_space_arch_matches_legacy():
    """The M-kind (SSM) layer node path: conv + state caches live as
    plain state tensors, scattered per-slot at admission."""
    cfg = configs.get_smoke("mamba2_130m")
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    ctx = steps.make_ctx(cfg, None)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, (4,)).astype(np.int32)

    logits, caches = jax.jit(
        lambda p, b: lm.prefill(p, b, cfg, ctx, max_seq=12)
    )(params, {"tokens": jnp.asarray(prompt)[None]})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    ref = [int(tok[0])]
    dstep = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, cfg, ctx))
    for _ in range(2):
        lg, caches = dstep(params, caches, tok)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        ref.append(int(tok[0]))

    b = Batcher(cfg, params, batch=2, max_seq=12)
    r = b.submit(prompt, max_new_tokens=3)
    b.run()
    assert r.generated == ref


def test_encdec_archs_rejected_by_graph_builders():
    cfg = configs.get_smoke("seamless_m4t_medium")
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    with pytest.raises(NotImplementedError):
        steps.make_decode_graph(cfg, params, batch=1, max_seq=8)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_serving_regions_embed_no_weights(which):
    """The serving graphs take the weights as executor inputs: no region
    executable may hold a constant above 1 MiB (a closed-over weight
    would be inlined as one — at published widths, gigabytes of HLO
    literals).  The vocab is widened so the embedding alone is 2 MiB."""
    from repro.analysis.hlo import HloCostModel, _shape_bytes
    from repro.core import Executor

    cfg = configs.get_smoke("qwen1_5_4b").with_(vocab_size=8192)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0), tp=1)
    assert params["embed"].nbytes > 1 << 20
    if which == "decode":
        g = steps.make_decode_graph(cfg, params, batch=2, max_seq=16)
        overrides = {}
    else:
        g = steps.make_prefill_graph(cfg, params, prompt_len=8, max_seq=16)
        overrides = {"prompt": jnp.ones((1, 8), jnp.int32)}
    ex = Executor(g.graph, donate=False)
    state = ex.init_state(**overrides, **g.weights.state(params))
    for i, region in enumerate(ex._regions):
        model = HloCostModel(ex.region_hlo(state, i))
        big = [(op.name, op.result_sig) for comp in model.comps.values()
               for op in comp.ops
               if op.opcode == "constant"
               and _shape_bytes(op.result_sig) > 1 << 20]
        assert not big, f"region {i} embeds constants: {big}"
    # the weights are read-only state: passed in, never donated or copied
    out = ex(state)
    for t in g.weights.tensors:
        assert out[t.name] is state[t.name]
