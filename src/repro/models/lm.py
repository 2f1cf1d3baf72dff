"""LM assembly: init, train forward (loss), prefill, and decode for every
assigned architecture, built from the uniform layer blocks.

Layers are stacked into scan groups (``cfg.pattern`` repeats; e.g. gemma3
scans 8 groups of [L,L,L,L,L,A], recurrentgemma scans 12 of [R,R,A] plus a
[R,R] tail) so the HLO stays small enough to compile 40 dry-run cells x 2
meshes on one CPU core, and so remat policy applies per group.

Sharding: all weight placement comes from logical axes (models/common);
activations are constrained to batch-over-DP at layer boundaries; the
vocab-sharded logits/CE never materialize an unsharded (B, S, V) array.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .blocks import (ShardCtx, init_layer, init_norm, layer_decode,
                     layer_forward, make_layer_cache, norm_apply)
from .common import ParamTree, count_params, stack_specs
from .config import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_group_stack(pt: ParamTree, cfg: ModelConfig, pattern, n_groups: int,
                      tp: int, *, cross: bool, name: str) -> None:
    specs = []

    def init_group(key):
        g = ParamTree(key, pt.dtype)
        for i, kind in enumerate(pattern):
            init_layer(g, cfg, kind, tp, cross=cross, name=f"p{i}")
        specs.append(g.specs)
        return g.params

    # one vmapped trace draws every group straight into the scan-ready
    # stacked arrays (the keys are those pt.child() would hand out), so
    # no per-group copy is ever stacked
    keys = jnp.stack([pt._next() for _ in range(n_groups)])
    pt.params[name] = jax.vmap(init_group)(keys)
    pt.specs[name] = stack_specs(specs[0])


def init_lm(cfg: ModelConfig, key: jax.Array, tp: int = 1):
    """-> (params, logical-spec tree).

    The params come out of one jitted program, so the device holds only
    the final tree and no intermediate of the init (at published widths
    a per-layer copy of the stacked weights would double their
    footprint)."""
    specs = []   # strings, not arrays: recorded by an abstract trace
    jax.eval_shape(lambda k: specs.append(_build_lm(cfg, k, tp)[1]), key)
    return _init_params(cfg, key, tp), specs[0]


@partial(jax.jit, static_argnums=(0, 2))
def _init_params(cfg: ModelConfig, key: jax.Array, tp: int):
    return _build_lm(cfg, key, tp)[0]


def _build_lm(cfg: ModelConfig, key: jax.Array, tp: int):
    pt = ParamTree(key, dtype=cfg.param_jdtype)
    Vp = cfg.padded_vocab(tp)
    d = cfg.d_model
    pt.dense("embed", (Vp, d), ("vocab", "embed"), fan_in=d)
    if cfg.frontend_dim:
        pt.dense("frontend_proj", (cfg.frontend_dim, d), (None, "embed"),
                 fan_in=cfg.frontend_dim)
    if cfg.is_encdec:
        _init_group_stack(pt, cfg, ("A",), cfg.enc_layers, tp,
                          cross=False, name="encoder")
        enc_norm = pt.child()
        init_norm(enc_norm, cfg, "ln", d)
        pt.sub("enc_final", enc_norm)
    n_groups, pattern, tail = cfg.layer_groups()
    _init_group_stack(pt, cfg, pattern, n_groups, tp,
                      cross=cfg.is_encdec, name="groups")
    for i, kind in enumerate(tail):
        t = pt.child()
        init_layer(t, cfg, kind, tp, cross=cfg.is_encdec, name="layer")
        pt.sub(f"tail{i}", t)
    fin = pt.child()
    init_norm(fin, cfg, "ln", d)
    pt.sub("final", fin)
    if not cfg.tie_embeddings:
        pt.dense("head", (Vp, d), ("vocab", "embed"), fan_in=d)
    return pt.params, pt.specs


def param_count(cfg: ModelConfig, tp: int = 1) -> int:
    shapes = jax.eval_shape(
        lambda k: init_lm(cfg, k, tp)[0], jax.random.PRNGKey(0))
    return sum(int(math.prod(s.shape)) for s in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# embedding / logits / loss
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig, ctx: ShardCtx):
    h = params["embed"].astype(cfg.compute_jdtype)[tokens]
    if cfg.scale_embed:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return h


def lm_logits(params, h, cfg: ModelConfig, ctx: ShardCtx):
    w = params.get("head", params["embed"]).astype(h.dtype)
    logits = jnp.einsum("...d,vd->...v", h, w)
    logits = ctx.constrain(logits, P(ctx.ba, *([None] * (logits.ndim - 2)),
                                     ctx.rules.get("vocab")))
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    Vp = w.shape[0]
    if Vp != cfg.vocab_size:
        mask = jnp.arange(Vp) < cfg.vocab_size
        logits = jnp.where(mask, logits, NEG_INF)
    return logits


def ce_loss(logits: jax.Array, labels: jax.Array):
    """Mean CE over positions with label >= 0."""
    valid = (labels >= 0)
    lab = jnp.maximum(labels, 0)
    lg = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
    per_tok = (lse - ll) * valid
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(per_tok) / n


# ---------------------------------------------------------------------------
# encoder (enc-dec archs)
# ---------------------------------------------------------------------------

def encode(params, frames, cfg: ModelConfig, ctx: ShardCtx):
    """frames (B, S_enc, frontend_dim) from the modality stub -> enc_out."""
    h = frames.astype(cfg.compute_jdtype) @ \
        params["frontend_proj"].astype(cfg.compute_jdtype)
    h = ctx.constrain(h, P(ctx.ba, None, None))

    def gfn(carry, gp):
        h = carry
        h, _, _ = layer_forward(gp["p0"], h, "A", cfg, ctx, causal=False)
        return h, None

    body = jax.checkpoint(gfn) if cfg.remat == "full" else gfn
    h, _ = lax.scan(body, h, params["encoder"])
    return norm_apply(params["enc_final"], h, cfg, "ln")


# ---------------------------------------------------------------------------
# full-sequence decoder pass (train / prefill)
# ---------------------------------------------------------------------------

def decoder_pass(params, h, cfg: ModelConfig, ctx: ShardCtx, *,
                 positions=None, enc_out=None, want_cache=False):
    """-> (h, aux_loss, caches|None); caches = {"groups": stacked, "tail": [...]}"""
    n_groups, pattern, tail = cfg.layer_groups()

    def gfn(carry, gp):
        h = carry
        aux_t = jnp.zeros((), jnp.float32)
        caches = {}
        for i, kind in enumerate(pattern):
            h, aux, c = layer_forward(gp[f"p{i}"], h, kind, cfg, ctx,
                                      causal=True, positions=positions,
                                      enc_out=enc_out,
                                      want_cache=want_cache)
            aux_t = aux_t + aux
            if want_cache:
                caches[f"p{i}"] = c
        return h, (aux_t, caches if want_cache else None)

    body = jax.checkpoint(gfn) if cfg.remat == "full" else gfn
    h, (auxs, group_caches) = lax.scan(body, h, params["groups"])
    aux_total = jnp.sum(auxs)
    tail_caches = []
    for i, kind in enumerate(tail):
        h, aux, c = layer_forward(params[f"tail{i}"]["layer"], h, kind, cfg,
                                  ctx, causal=True, positions=positions,
                                  enc_out=enc_out, want_cache=want_cache)
        aux_total = aux_total + aux
        tail_caches.append(c)
    h = norm_apply(params["final"], h, cfg, "ln")
    caches = None
    if want_cache:
        caches = {"groups": group_caches, "tail": tail_caches}
    return h, aux_total, caches


def assemble_input(params, batch, cfg: ModelConfig, ctx: ShardCtx):
    """Token (+frontend) embeddings -> (h, positions, enc_out)."""
    tokens = batch["tokens"]
    h = embed_tokens(params, tokens, cfg, ctx)
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, batch["frames"], cfg, ctx)
    elif cfg.frontend_dim and "patches" in batch:
        pe = batch["patches"].astype(h.dtype) @ \
            params["frontend_proj"].astype(h.dtype)
        h = jnp.concatenate([pe, h], axis=1)
    h = ctx.constrain(h, P(ctx.ba, None, None))
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    return h, positions, enc_out


def forward_loss(params, batch, cfg: ModelConfig, ctx: ShardCtx, *,
                 aux_weight: float = 0.01):
    """Training objective: CE + aux (MoE load-balance) loss."""
    h, positions, enc_out = assemble_input(params, batch, cfg, ctx)
    h, aux, _ = decoder_pass(params, h, cfg, ctx, positions=positions,
                             enc_out=enc_out)
    logits = lm_logits(params, h, cfg, ctx)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:  # vlm: frontend positions
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    loss = ce_loss(logits, labels)
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(params, cfg: ModelConfig, batch: int, max_seq: int,
                ctx: ShardCtx, *, enc_len: int = 0):
    """Empty cache pytree matching decoder_pass(want_cache) structure,
    converted for decode (attention caches sized to max_seq / window)."""
    n_groups, pattern, tail = cfg.layer_groups()
    tp = ctx.tp
    dt = cfg.compute_jdtype

    def one(kind):
        c = make_layer_cache(kind, cfg, batch, max_seq, dt, tp)
        if cfg.is_encdec and kind in ("A", "L"):
            cross = make_layer_cache("A", cfg, batch, max(enc_len, 1), dt, tp)
            return {"self": c, "cross": cross}
        return c

    groups = {f"p{i}": jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_groups, *x.shape)).copy(), one(k))
        for i, k in enumerate(pattern)}
    return {"groups": groups,
            "tail": [one(k) for k in tail],
            "pos": jnp.zeros((), jnp.int32)}


def _prefill_to_decode_cache(raw, kind, cfg: ModelConfig, batch, max_seq,
                             dtype, tp):
    """Convert a layer_forward cache emission into decode-ready storage."""
    from .blocks import fill_attn_cache, make_attn_cache
    if kind in ("A", "L"):
        k, v = raw
        window = cfg.window if kind == "L" else None
        store = make_attn_cache(cfg, batch, max_seq, window, dtype, tp)
        return fill_attn_cache(store, k, v, cfg, window)
    return raw  # ssm/rglru states are already decode-ready


def prefill(params, batch, cfg: ModelConfig, ctx: ShardCtx, *,
            max_seq: Optional[int] = None):
    """Process the prompt; -> (last-token logits (B, Vp), caches)."""
    h, positions, enc_out = assemble_input(params, batch, cfg, ctx)
    B, S = h.shape[0], h.shape[1]
    max_seq = max_seq or S
    h, _, raw = decoder_pass(params, h, cfg, ctx, positions=positions,
                             enc_out=enc_out, want_cache=True)
    n_groups, pattern, tail = cfg.layer_groups()
    dt = cfg.compute_jdtype

    def conv_group(i, kind):
        entry = jax.tree.map(
            lambda *_: None, None)  # placeholder, replaced below
        raw_i = raw["groups"][f"p{i}"]
        conv = jax.vmap(
            lambda r: _prefill_to_decode_cache(r, kind, cfg, B, max_seq,
                                               dt, ctx.tp))(raw_i)
        if cfg.is_encdec and kind in ("A", "L"):
            # cross-attention cache: encoder k/v per group layer
            def cross_of(gp):
                p = gp[f"p{i}"]["cross"]
                k = jnp.einsum("bsd,dhk->bshk", enc_out,
                               p["wk"].astype(dt))
                v = jnp.einsum("bsd,dhk->bshk", enc_out,
                               p["wv"].astype(dt))
                store = _prefill_to_decode_cache((k, v), "A", cfg, B,
                                                 enc_out.shape[1], dt, ctx.tp)
                return store
            cross = jax.lax.map(cross_of, params["groups"])
            return {"self": conv, "cross": cross}
        return conv

    groups = {f"p{i}": conv_group(i, k) for i, k in enumerate(pattern)}
    tails = []
    for i, kind in enumerate(tail):
        c = _prefill_to_decode_cache(raw["tail"][i], kind, cfg, B, max_seq,
                                     dt, ctx.tp)
        if cfg.is_encdec and kind in ("A", "L"):
            p = params[f"tail{i}"]["layer"]["cross"]
            k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
            c = {"self": c,
                 "cross": _prefill_to_decode_cache((k, v), "A", cfg, B,
                                                   enc_out.shape[1], dt,
                                                   ctx.tp)}
        tails.append(c)
    caches = {"groups": groups, "tail": tails,
              "pos": jnp.asarray(S, jnp.int32)}
    logits = lm_logits(params, h[:, -1], cfg, ctx)
    return logits, caches


def decode_step(params, caches, tokens_t, cfg: ModelConfig, ctx: ShardCtx, *,
                enc_len: Optional[int] = None):
    """One token for the whole batch. tokens_t (B,) -> (logits, caches)."""
    pos = caches["pos"]
    h_t = embed_tokens(params, tokens_t, cfg, ctx)
    h_t = ctx.constrain(h_t, P(ctx.ba, None))
    n_groups, pattern, tail = cfg.layer_groups()

    def gfn(carry, xs):
        h_t = carry
        gp, gc = xs
        new_c = {}
        for i, kind in enumerate(pattern):
            c = gc[f"p{i}"]
            if isinstance(c, dict):  # encdec
                h_t, cs = layer_decode(gp[f"p{i}"], h_t, kind, cfg, ctx,
                                       cache=c["self"], pos=pos,
                                       enc_cache=c["cross"], enc_len=enc_len)
                new_c[f"p{i}"] = {"self": cs, "cross": c["cross"]}
            else:
                h_t, cs = layer_decode(gp[f"p{i}"], h_t, kind, cfg, ctx,
                                       cache=c, pos=pos)
                new_c[f"p{i}"] = cs
        return h_t, new_c

    h_t, new_groups = lax.scan(gfn, h_t, (params["groups"], caches["groups"]))
    new_tail = []
    for i, kind in enumerate(tail):
        c = caches["tail"][i]
        if isinstance(c, dict):
            h_t, cs = layer_decode(params[f"tail{i}"]["layer"], h_t, kind,
                                   cfg, ctx, cache=c["self"], pos=pos,
                                   enc_cache=c["cross"], enc_len=enc_len)
            new_tail.append({"self": cs, "cross": c["cross"]})
        else:
            h_t, cs = layer_decode(params[f"tail{i}"]["layer"], h_t, kind,
                                   cfg, ctx, cache=c, pos=pos)
            new_tail.append(cs)
    h_t = norm_apply(params["final"], h_t, cfg, "ln")
    logits = lm_logits(params, h_t, cfg, ctx)
    return logits, {"groups": new_groups, "tail": new_tail, "pos": pos + 1}
