"""Step builders: sharded train / prefill / decode step functions and the
ShapeDtypeStruct input/state specs the multi-pod dry-run lowers against.

Sharding scheme (DESIGN.md §5):

  train/prefill   batch over DP axes ("pod","data"); heads / d_ff / vocab /
                  expert-TP over "model"; experts over "data" (explicit-a2a
                  EP); residual d_model over "model" between layers
                  (Megatron SP) so remat-saved carries are TP-sharded;
                  optimizer moments additionally over DP (ZeRO-1).
  decode          batch over DP; KV-cache *sequence* over "model"
                  (flash-decoding LSE combine); ring caches replicated.
  long_500k (B=1) cache sequence over ALL axes; batch unsharded.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import Graph
from repro.core.layout import RecordArray
from repro.core.tensor import DistTensor
from repro.models import kvcache as kvc
from repro.models.blocks import ShardCtx, layer_decode, norm_apply
from repro.models.common import DEFAULT_RULES, spec_tree_to_pspecs
from repro.models.config import ModelConfig, ShapeCfg
from repro.models.lm import (_prefill_to_decode_cache, decode_step,
                             decoder_pass, embed_tokens, forward_loss,
                             init_caches, init_lm, lm_logits, prefill)
from repro.models.moe import make_moe_a2a
from repro.optim import clip_by_global_norm, cosine_schedule, make_optimizer
from .mesh import dp_axes, tp_size

ENC_LEN_SERVE = 4096  # frozen encoder length for enc-dec decode cells


# ---------------------------------------------------------------------------
# rules / ctx
# ---------------------------------------------------------------------------

def fsdp_pspec(shape: tuple, mesh: Mesh) -> P:
    """ZeRO-3 placement: shard the first dim divisible by the flat mesh
    (data x model), falling back to model-only / data-only / replicated.
    XLA inserts the per-layer weight all-gather inside the layer scan."""
    axes_options = [tuple(a for a in ("data", "model")
                          if mesh.shape.get(a, 1) > 1),
                    ("model",), ("data",)]
    for axes in axes_options:
        if not axes or any(a not in mesh.shape for a in axes):
            continue
        n = math.prod(mesh.shape[a] for a in axes)
        if n <= 1:
            continue
        for i, s in enumerate(shape):
            if s % n == 0 and s >= n:
                entries: list = [None] * len(shape)
                entries[i] = axes if len(axes) > 1 else axes[0]
                return P(*entries)
    return P()


def make_rules(cfg: ModelConfig, mesh: Mesh) -> dict:
    rules = dict(DEFAULT_RULES)
    tp = tp_size(mesh)
    if tp <= 1 or cfg.train_sharding == "fsdp":
        return {k: None for k in rules}
    if cfg.n_kv_heads % tp:
        rules["kv_heads"] = None
    if cfg.d_ff and cfg.d_ff % tp:
        rules["ff"] = None
    if cfg.lru_width and cfg.lru_width % tp:
        rules["rnn"] = None
    if cfg.n_experts:
        data = mesh.shape.get("data", 1)
        if data > 1 and cfg.n_experts % data == 0:
            rules["experts"] = "data"
            rules["expert_ff"] = "model" if cfg.d_ff % tp == 0 else None
        elif cfg.n_experts % tp == 0:
            rules["experts"] = "model"
            rules["expert_ff"] = None
        else:
            rules["experts"] = None
            rules["expert_ff"] = "model" if cfg.d_ff % tp == 0 else None
    return rules


def make_ctx(cfg: ModelConfig, mesh: Optional[Mesh],
             shape: Optional[ShapeCfg] = None) -> ShardCtx:
    if mesh is None:
        return ShardCtx()
    rules = make_rules(cfg, mesh)
    dp = dp_axes(mesh)
    batch_axes: tuple = dp
    seq_axes: tuple = ()
    moe_a2a = None
    if cfg.train_sharding == "fsdp" and (shape is None
                                         or shape.kind == "train"):
        # batch over as many axes as divide the PER-MICROBATCH batch
        # (ZeRO-3 data parallelism; grad accumulation shrinks the live
        # batch, so mb > 1 can force dp-only sharding — see EXPERIMENTS
        # §Perf cell 1 iter 4, where the naive combination replicated
        # compute 2x)
        B = (shape.global_batch // max(cfg.microbatches, 1)
             if shape is not None else 0)
        for cand in (dp + ("model",), dp):
            n = math.prod(mesh.shape[a] for a in cand)
            if B == 0 or B % n == 0:
                batch_axes = cand
                break
        return ShardCtx(mesh=mesh, rules=rules, batch_axes=batch_axes,
                        residual_tp=False)
    if shape is not None and shape.is_decode:
        if shape.global_batch == 1:
            batch_axes = ()
            seq_axes = tuple(mesh.axis_names)       # all axes shard the cache
        else:
            seq_axes = ("model",) if tp_size(mesh) > 1 else ()
    elif cfg.n_experts and rules.get("experts") == "data" \
            and (shape is None or not shape.is_decode):
        moe_a2a = make_moe_a2a(mesh, dp_axes=dp, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               residual_tp=cfg.shard_activations)
    return ShardCtx(mesh=mesh, rules=rules, batch_axes=batch_axes,
                    decode_seq_axes=seq_axes,
                    residual_tp=cfg.shard_activations and tp_size(mesh) > 1,
                    moe_a2a=moe_a2a)


# ---------------------------------------------------------------------------
# params: shapes + shardings (no allocation)
# ---------------------------------------------------------------------------

def param_specs(cfg: ModelConfig, mesh: Mesh):
    """-> (param ShapeDtypeStructs WITH shardings, pspec tree)."""
    tp = 1 if cfg.train_sharding == "fsdp" else tp_size(mesh)
    shapes = jax.eval_shape(lambda k: init_lm(cfg, k, tp)[0],
                            jax.random.PRNGKey(0))
    spec_tree = init_specs_only(cfg, tp)
    rules = make_rules(cfg, mesh)
    pspecs = spec_tree_to_pspecs(spec_tree, rules)
    if cfg.train_sharding == "fsdp":
        pspecs = jax.tree.map(lambda s: fsdp_pspec(s.shape, mesh), shapes)
    sds = jax.tree.map(
        lambda s, ps: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, ps)),
        shapes, pspecs)
    return sds, pspecs


_SPEC_CACHE: dict = {}


def init_specs_only(cfg: ModelConfig, tp: int):
    key = (cfg, tp)
    if key not in _SPEC_CACHE:
        # tracing init_lm just for the spec tree is cheap under eval_shape;
        # specs are returned as aux (static python objects survive)
        holder = {}

        def fn(k):
            p, s = init_lm(cfg, k, tp)
            holder["specs"] = s
            return p

        jax.eval_shape(fn, jax.random.PRNGKey(0))
        _SPEC_CACHE[key] = holder["specs"]
    return _SPEC_CACHE[key]


# ---------------------------------------------------------------------------
# input specs per shape cell
# ---------------------------------------------------------------------------

def batch_arrays(cfg: ModelConfig, shape: ShapeCfg, *, np_like=False):
    """Concrete small-dtype host arrays for smoke runs (unsharded)."""
    import numpy as np
    B, S = shape.global_batch, shape.seq_len
    S_text = S - (cfg.frontend_tokens if cfg.frontend_dim
                  and not cfg.is_encdec else 0)
    rng = np.random.default_rng(0)
    out = {"tokens": rng.integers(0, cfg.vocab_size, (B, S_text),
                                  dtype=np.int32)}
    if shape.kind == "train":
        out["labels"] = rng.integers(0, cfg.vocab_size, (B, S_text),
                                     dtype=np.int32)
    if cfg.is_encdec:
        enc = S if shape.kind == "train" else ENC_LEN_SERVE
        out["frames"] = rng.standard_normal((B, enc, cfg.frontend_dim)
                                            ).astype(np.float32)
    elif cfg.frontend_dim:
        out["patches"] = rng.standard_normal(
            (B, cfg.frontend_tokens, cfg.frontend_dim)).astype(np.float32)
    return out


def input_specs(cfg: ModelConfig, shape: ShapeCfg, mesh: Mesh):
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no
    allocation) for every model input of this (arch x shape) cell."""
    ctx = make_ctx(cfg, mesh, shape)
    ba = ctx.ba
    cdt = cfg.compute_jdtype

    def sds(shape_, dtype, spec):
        return jax.ShapeDtypeStruct(shape_, dtype,
                                    sharding=NamedSharding(mesh, spec))

    B, S = shape.global_batch, shape.seq_len
    if shape.is_decode:
        return {"tokens": sds((B,), jnp.int32, P(ba))}
    S_text = S - (cfg.frontend_tokens if cfg.frontend_dim
                  and not cfg.is_encdec else 0)
    out = {"tokens": sds((B, S_text), jnp.int32, P(ba, None))}
    if shape.kind == "train":
        out["labels"] = sds((B, S_text), jnp.int32, P(ba, None))
    if cfg.is_encdec:
        enc = S if shape.kind == "train" else ENC_LEN_SERVE
        out["frames"] = sds((B, enc, cfg.frontend_dim), cdt, P(ba, None, None))
    elif cfg.frontend_dim:
        out["patches"] = sds((B, cfg.frontend_tokens, cfg.frontend_dim), cdt,
                             P(ba, None, None))
    return out


# ---------------------------------------------------------------------------
# decode cache specs
# ---------------------------------------------------------------------------

def cache_pspecs(cfg: ModelConfig, ctx: ShardCtx):
    """PartitionSpec tree exactly mirroring init_caches structure."""
    ba = ctx.ba
    sa = tuple(ctx.decode_seq_axes) or None

    def kv_specs(seq_sharded: bool, lead: bool):
        ps = kvc.kv_pspec(cfg.kv_layout, batch_axes=ctx.batch_axes,
                          seq_axes=(sa if seq_sharded else None),
                          order=cfg.kv_order)
        return P(None, *ps) if lead else ps

    def entry(kind: str, lead: bool):
        ldim = (None,) if lead else ()
        if kind == "A":
            e = kv_specs(True, lead)
        elif kind == "L":
            e = kv_specs(False, lead)
        elif kind == "M":
            e = (P(*ldim, ba, "model" if ctx.tp > 1 else None, None, None),
                 P(*ldim, ba, None, None))
        elif kind == "R":
            r = "model" if (ctx.tp > 1 and cfg.lru_width % ctx.tp == 0) \
                else None
            e = (P(*ldim, ba, r), P(*ldim, ba, None, r))
        else:
            raise ValueError(kind)
        if cfg.is_encdec and kind in ("A", "L"):
            return {"self": e, "cross": kv_specs(True, lead)}
        return e

    n_groups, pattern, tail = cfg.layer_groups()
    return {"groups": {f"p{i}": entry(k, True)
                       for i, k in enumerate(pattern)},
            "tail": [entry(k, False) for k in tail],
            "pos": P()}


def decode_state_specs(cfg: ModelConfig, shape: ShapeCfg, mesh: Mesh):
    """ShapeDtypeStructs (with shardings) for the decode cache pytree."""
    ctx = make_ctx(cfg, mesh, shape)
    B, S = shape.global_batch, shape.seq_len
    enc_len = ENC_LEN_SERVE if cfg.is_encdec else 0
    shapes = jax.eval_shape(
        lambda: init_caches(None, cfg, B, S, ctx, enc_len=enc_len))
    pspecs = cache_pspecs(cfg, ctx)
    return jax.tree.map(
        lambda s, ps: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, ps)),
        shapes, pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)), pspecs


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh: Optional[Mesh], *,
                    lr=None, total_steps: int = 10_000,
                    clip_norm: float = 1.0):
    """-> train_step(state, batch) -> (state, metrics); state = {params,
    opt, step}."""
    ctx = make_ctx(cfg, mesh, None)
    opt = make_optimizer(cfg.optimizer,
                         lr or cosine_schedule(3e-4, 200, total_steps))
    k = cfg.microbatches

    def loss_fn(params, mb):
        return forward_loss(params, mb, cfg, ctx)

    def train_step(state, batch):
        params = state["params"]
        if k > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch)

            def body(acc, mb):
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                acc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), acc, g)
                return acc, (loss, metrics)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, _) = lax.scan(body, zeros, mbs)
            grads = jax.tree.map(lambda g: g / k, grads)
            loss = jnp.mean(losses)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        new_params, new_opt = opt.update(grads, state["opt"], params,
                                         state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss.astype(jnp.float32),
                           "grad_norm": gnorm.astype(jnp.float32)}

    return train_step, opt


def train_state_specs(cfg: ModelConfig, mesh: Mesh, opt):
    """ShapeDtypeStructs + shardings for the full train state."""
    p_sds, p_pspecs = param_specs(cfg, mesh)
    o_shapes = jax.eval_shape(opt.init, p_sds)
    o_pspecs = opt.state_pspecs(p_sds, p_pspecs, mesh, dp_axes(mesh),
                                zero1=cfg.zero1)
    o_sds = jax.tree.map(
        lambda s, ps: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, ps)),
        o_shapes, o_pspecs)
    step_sds = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
    state_sds = {"params": p_sds, "opt": o_sds, "step": step_sds}
    state_pspecs = {"params": p_pspecs, "opt": o_pspecs, "step": P()}
    return state_sds, state_pspecs


def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh],
                      shape: Optional[ShapeCfg] = None):
    ctx = make_ctx(cfg, mesh, shape)

    def prefill_step(params, batch):
        return prefill(params, batch, cfg, ctx)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh: Optional[Mesh],
                     shape: Optional[ShapeCfg] = None):
    ctx = make_ctx(cfg, mesh, shape)
    enc_len = ENC_LEN_SERVE if cfg.is_encdec else None

    def step(params, caches, tokens):
        logits, caches = decode_step(params, caches, tokens, cfg, ctx,
                                     enc_len=enc_len)
        return logits, caches

    return step


# ---------------------------------------------------------------------------
# graph-native serving: prefill + batched greedy decode as Ripple graphs
# ---------------------------------------------------------------------------
#
# The decode step becomes a Graph with one node per unrolled layer.  Every
# attention/sliding-window cache is a *record* DistTensor (fields k, v over
# the (B, S, Hkv) / (B, Hkv, S) space) so the layout solver / measured
# autotuner — not the model code — picks AoS / SoA / AoSoA storage.  The
# node fn reads the RecordArray's layout at trace time and re-derives the
# ModelConfig under it, which makes the model code layout-polymorphic
# without a single `if` at the call site.
#
# Weights are executor state: every node takes the parameter leaves as
# tensor args (read-only, so the executor passes them into each region
# executable as inputs — never embedded as constants, never donated or
# copied).  Zero-trace serving: node fns close over (cfg, ctx, the weight
# handles).  The ctx is cached per (cfg, mesh, shape) below, and graphs per
# (cfg, params) object, so a worker process that rebuilds them from the
# SAME cfg/params objects produces an identical plan signature and serves
# straight from the process-wide executable cache.

_CTX_CACHE: dict = {}


def _serving_ctx(cfg: ModelConfig, mesh: Optional[Mesh],
                 shape: ShapeCfg) -> ShardCtx:
    """make_ctx with an id-stable result (the executable-cache signature
    keys closure cells by object identity)."""
    key = (cfg, None if mesh is None else id(mesh), shape)
    if key not in _CTX_CACHE:
        _CTX_CACHE[key] = make_ctx(cfg, mesh, shape)
    return _CTX_CACHE[key]


@dataclass(frozen=True)
class CacheSlot:
    """One decode-cache layer lifted into named executor state tensors.

    ``group``/``part`` address the layer inside the legacy cache pytree
    (``caches["groups"]["p{part}"][group]``; ``group == -1`` -> tail layer
    ``caches["tail"][part]``).  ``tensors`` is one record DistTensor for
    attention kinds (A/L) and two plain DistTensors for state-space kinds
    (M: ssm state + conv buffer; R: rg-lru state + conv buffer)."""

    label: str
    kind: str
    group: int
    part: int
    tensors: tuple


def _slot_tensors(cfg: ModelConfig, label: str, kind: str, batch: int,
                  max_seq: int, tp: int) -> tuple:
    dt = cfg.compute_jdtype
    if kind in ("A", "L"):
        S = min(cfg.window, max_seq) if kind == "L" else max_seq
        Hkv = cfg.padded_kv_heads(tp)
        space = ((batch, S, Hkv) if cfg.kv_order == "bsh"
                 else (batch, Hkv, S))
        return (DistTensor(f"kv_{label}", space, dtype=dt,
                           spec=kvc.kv_spec(cfg.head_dim),
                           layout=cfg.kv_layout),)
    if kind == "M":
        H = cfg.padded_ssm_heads(tp)
        P_, N, K = cfg.ssm_head_dim, cfg.ssm_state, cfg.d_conv
        return (DistTensor(f"ssm_{label}", (batch, H, P_, N),
                           dtype=jnp.float32),
                DistTensor(f"cv_{label}", (batch, K - 1, H * P_ + 2 * N),
                           dtype=dt))
    if kind == "R":
        R, K = cfg.lru_width, cfg.d_conv
        return (DistTensor(f"rg_{label}", (batch, R), dtype=jnp.float32),
                DistTensor(f"cv_{label}", (batch, K - 1, R), dtype=dt))
    raise ValueError(kind)


def serving_cache_slots(cfg: ModelConfig, batch: int, max_seq: int,
                        tp: int = 1) -> tuple:
    """Every decode-cache layer as a CacheSlot, in legacy scan order
    (g0p0, g0p1, ..., g1p0, ..., tail0, ...) so graph-native decode visits
    layers exactly like ``decode_step``'s lax.scan."""
    n_groups, pattern, tail = cfg.layer_groups()
    slots = []
    for gi in range(n_groups):
        for pi, kind in enumerate(pattern):
            label = f"g{gi}p{pi}"
            slots.append(CacheSlot(label, kind, gi, pi,
                                   _slot_tensors(cfg, label, kind, batch,
                                                 max_seq, tp)))
    for ti, kind in enumerate(tail):
        label = f"t{ti}"
        slots.append(CacheSlot(label, kind, -1, ti,
                               _slot_tensors(cfg, label, kind, batch,
                                             max_seq, tp)))
    return tuple(slots)


@dataclass(frozen=True)
class Weights:
    """Model parameters as read-only executor state: one plain DistTensor
    per parameter leaf, named by its tree path."""

    treedef: Any
    tensors: tuple

    @classmethod
    def of(cls, params) -> "Weights":
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        return cls(treedef, tuple(
            DistTensor("w:" + jax.tree_util.keystr(path, simple=True,
                                                   separator="/"),
                       x.shape, dtype=x.dtype)
            for path, x in leaves))

    def tree(self, leaves):
        """The parameter tree from the node args bound to ``tensors``."""
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def state(self, params) -> dict:
        """``Executor.init_state`` overrides binding ``params``."""
        return {t.name: x
                for t, x in zip(self.tensors, jax.tree.leaves(params))}


def _slot_params(params, gi: int, pi: int):
    if gi < 0:
        return params[f"tail{pi}"]["layer"]
    return jax.tree.map(lambda x: x[gi], params["groups"][f"p{pi}"])


def _guard_graph_serving(cfg: ModelConfig) -> None:
    if cfg.is_encdec or cfg.frontend_dim:
        raise NotImplementedError(
            f"{cfg.name}: graph-native serving covers text-only decoder "
            f"archs; encoder-decoder / VLM archs serve through the legacy "
            f"jit path (launch/serve.py falls back automatically)")


def _embed_node(cfg: ModelConfig, ctx: ShardCtx, w: Weights):
    def embed(tokens_t, h_t, *leaves):
        return embed_tokens(w.tree(leaves), tokens_t, cfg, ctx)
    return embed


def _attn_layer_node(cfg: ModelConfig, ctx: ShardCtx, w: Weights,
                     slot: CacheSlot):
    gi, pi, kind = slot.group, slot.part, slot.kind

    def layer(h_t, kv, pos, *leaves):
        # the solver's layout choice arrives on the RecordArray; re-derive
        # the config under it so the kernel code is layout-polymorphic
        lcfg = cfg.with_(kv_layout=kv.layout)
        p = _slot_params(w.tree(leaves), gi, pi)
        h2, store = layer_decode(p, h_t, kind, lcfg, ctx,
                                 cache=kv.data, pos=pos)
        return h2, RecordArray(store, kv.spec, kv.layout)

    return layer


def _state_layer_node(cfg: ModelConfig, ctx: ShardCtx, w: Weights,
                      slot: CacheSlot):
    gi, pi, kind = slot.group, slot.part, slot.kind

    def layer(h_t, s0, s1, pos, *leaves):
        p = _slot_params(w.tree(leaves), gi, pi)
        h2, (n0, n1) = layer_decode(p, h_t, kind, cfg, ctx,
                                    cache=(s0, s1), pos=pos)
        return h2, n0, n1

    return layer


def _head_node(cfg: ModelConfig, ctx: ShardCtx, w: Weights):
    def head(h_t, tokens_t, pos, active, *leaves):
        params = w.tree(leaves)
        hn = norm_apply(params["final"], h_t, cfg, "ln")
        logits = lm_logits(params, hn, cfg, ctx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tokens_t)
        return nxt, pos + active.astype(jnp.int32)
    return head


@dataclass(frozen=True)
class DecodeGraph:
    """Graph + tensor handles for one batched greedy-decode step.

    State layout: ``tokens``/``pos``/``active`` are (B,) per-slot vectors
    (continuous batching: every batch slot sits at its own depth; inactive
    slots keep their token and don't advance), ``h`` is the (B, d_model)
    residual scratch, each CacheSlot contributes its cache tensors, and
    ``weights`` binds the parameters (``weights.state(params)``)."""

    graph: Graph
    tokens: DistTensor
    pos: DistTensor
    active: DistTensor
    h: DistTensor
    slots: tuple
    weights: Weights


@dataclass(frozen=True)
class PrefillGraph:
    """Graph + tensor handles for a single-request (B=1) prefill.

    Writes every decode-cache slot (batch=1) plus ``first`` — the argmax
    token following the prompt; the batcher scatters these into the decode
    state's batch slot at admission."""

    graph: Graph
    prompt: DistTensor
    hseq: DistTensor
    hlast: DistTensor
    first: DistTensor
    slots: tuple
    weights: Weights


def cache_state_overrides(cfg: ModelConfig, slots: tuple, caches) -> dict:
    """Map a legacy ``prefill()``/``init_caches()`` cache pytree onto the
    graph state names (``Executor.init_state(**overrides)`` kwargs).
    Attention storages arrive in ``cfg.kv_layout`` and are wrapped as
    RecordArrays so init_state relayouts them to the solver's choice."""
    out = {}
    for slot in slots:
        if slot.group < 0:
            entry = caches["tail"][slot.part]
        else:
            entry = jax.tree.map(lambda x: x[slot.group],
                                 caches["groups"][f"p{slot.part}"])
        if slot.kind in ("A", "L"):
            out[slot.tensors[0].name] = RecordArray(
                entry, kvc.kv_spec(cfg.head_dim), cfg.kv_layout)
        else:
            out[slot.tensors[0].name] = entry[0]
            out[slot.tensors[1].name] = entry[1]
    return out


_SERVE_GRAPH_CACHE: dict = {}


def make_decode_graph(cfg: ModelConfig, params, *, batch: int, max_seq: int,
                      mesh: Optional[Mesh] = None) -> DecodeGraph:
    """One greedy-decode step for ``batch`` slots as a Ripple graph.

    Node order mirrors ``decode_step``'s scan exactly (embed -> every
    unrolled layer in g0p0.. order -> final-norm/logits/argmax head) so
    the argmax token sequence is bit-identical to the legacy jit path."""
    _guard_graph_serving(cfg)
    key = ("decode", id(cfg), id(params), batch, max_seq,
           None if mesh is None else id(mesh))
    if key in _SERVE_GRAPH_CACHE:
        return _SERVE_GRAPH_CACHE[key]
    shape = ShapeCfg(f"serve_decode_b{batch}", "decode", max_seq, batch)
    ctx = _serving_ctx(cfg, mesh, shape)
    tp = 1 if mesh is None else tp_size(mesh)
    tokens = DistTensor("tokens", (batch,), dtype=jnp.int32)
    pos = DistTensor("pos", (batch,), dtype=jnp.int32)
    active = DistTensor("active", (batch,), dtype=jnp.bool_)
    h = DistTensor("h", (batch, cfg.d_model), dtype=cfg.compute_jdtype)
    slots = serving_cache_slots(cfg, batch, max_seq, tp)
    w = Weights.of(params)
    g = Graph(name=f"decode_{cfg.name}")
    g.then(_embed_node(cfg, ctx, w), args=(tokens, h, *w.tensors),
           writes=(1,))
    for slot in slots:
        if slot.kind in ("A", "L"):
            kv, = slot.tensors
            g.then(_attn_layer_node(cfg, ctx, w, slot),
                   args=(h, kv, pos, *w.tensors), writes=(0, 1))
        else:
            s0, s1 = slot.tensors
            g.then(_state_layer_node(cfg, ctx, w, slot),
                   args=(h, s0, s1, pos, *w.tensors), writes=(0, 1, 2))
    g.then(_head_node(cfg, ctx, w),
           args=(h, tokens, pos, active, *w.tensors), writes=(1, 2))
    out = DecodeGraph(g, tokens, pos, active, h, slots, w)
    _SERVE_GRAPH_CACHE[key] = out
    return out


def make_prefill_graph(cfg: ModelConfig, params, *, prompt_len: int,
                       max_seq: int,
                       mesh: Optional[Mesh] = None) -> PrefillGraph:
    """B=1 prompt processing as a Ripple graph: embed -> decoder pass
    (emitting every layer's decode-ready cache) -> first-token head.

    The cache writes are RecordArrays in ``cfg.kv_layout``; the executor
    relayouts them in-trace to whatever layout its solver chose, so the
    prefill and decode plans may disagree about storage freely."""
    _guard_graph_serving(cfg)
    key = ("prefill", id(cfg), id(params), prompt_len, max_seq,
           None if mesh is None else id(mesh))
    if key in _SERVE_GRAPH_CACHE:
        return _SERVE_GRAPH_CACHE[key]
    shape = ShapeCfg(f"serve_prefill_s{prompt_len}", "prefill",
                     prompt_len, 1)
    ctx = _serving_ctx(cfg, mesh, shape)
    tp = 1 if mesh is None else tp_size(mesh)
    dt = cfg.compute_jdtype
    prompt = DistTensor("prompt", (1, prompt_len), dtype=jnp.int32)
    hseq = DistTensor("hseq", (1, prompt_len, cfg.d_model), dtype=dt)
    hlast = DistTensor("hlast", (1, cfg.d_model), dtype=dt)
    first = DistTensor("first", (1,), dtype=jnp.int32)
    slots = serving_cache_slots(cfg, 1, max_seq, tp)
    flat = tuple(t for slot in slots for t in slot.tensors)
    w = Weights.of(params)

    def body(h_, hl_, *rest):
        params = w.tree(rest[len(flat):])
        positions = jnp.arange(h_.shape[1], dtype=jnp.int32)
        hh = ctx.constrain(h_, P(ctx.ba, None, None))
        hh, _, raw = decoder_pass(params, hh, cfg, ctx,
                                  positions=positions, want_cache=True)
        outs = []
        for slot in slots:
            if slot.group < 0:
                raw_entry = raw["tail"][slot.part]
            else:
                raw_entry = jax.tree.map(lambda x: x[slot.group],
                                         raw["groups"][f"p{slot.part}"])
            store = _prefill_to_decode_cache(raw_entry, slot.kind, cfg, 1,
                                             max_seq, dt, ctx.tp)
            if slot.kind in ("A", "L"):
                outs.append(RecordArray(store, kvc.kv_spec(cfg.head_dim),
                                        cfg.kv_layout))
            else:
                outs.extend(store)
        return (hh[:, -1], *outs)

    def head(hl_, first_, *leaves):
        logits = lm_logits(w.tree(leaves), hl_, cfg, ctx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    g = Graph(name=f"prefill_{cfg.name}_s{prompt_len}")
    g.then(_embed_node(cfg, ctx, w), args=(prompt, hseq, *w.tensors),
           writes=(1,))
    g.then(body, args=(hseq, hlast, *flat, *w.tensors),
           writes=tuple(range(1, 2 + len(flat))))
    g.then(head, args=(hlast, first, *w.tensors), writes=(1,))
    out = PrefillGraph(g, prompt, hseq, hlast, first, slots, w)
    _SERVE_GRAPH_CACHE[key] = out
    return out
