"""Model substrate: param trees with logical sharding axes, norms, RoPE.

Parameters are plain dict pytrees.  Alongside every param tree we carry a
parallel *spec tree* whose leaves are tuples of logical axis names
(e.g. ``("layer", "embed", "q_heads", "head_dim")``).  A per-config rules
table maps logical axes -> mesh axes, giving each arch its TP/EP layout
without touching layer code (same philosophy as the paper's polymorphic
layout: the storage decision is a single declarative knob, the compute is
written once).

Logical axes used across the stack:
  layer / group      scan axis over (groups of) layers           -> never sharded
  embed              d_model                                      -> never sharded
  q_heads            attention query heads (padded to TP)         -> "model"
  kv_heads           attention kv heads                           -> "model" iff divisible
  head_dim           per-head dim                                 -> never sharded
  ff                 MLP hidden                                   -> "model"
  vocab              (padded) vocabulary                          -> "model"
  experts            MoE experts                                  -> "model"
  ssm_heads          Mamba2 value heads (padded)                  -> "model"
  ssm_state / conv   SSD state dim / conv kernel                  -> never sharded
  rnn                RG-LRU recurrent width                       -> "model"
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# logical axis -> mesh axis resolution
# ---------------------------------------------------------------------------

DEFAULT_RULES: dict[str, Optional[str]] = {
    "layer": None,
    "group": None,
    "embed": None,
    "head_dim": None,
    "q_heads": "model",
    "kv_heads": "model",     # dropped to None by configs when not divisible
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ff": None,   # production rules move experts->data, expert_ff->model
    "ssm_heads": "model",
    "ssm_state": None,
    "conv": None,
    "rnn": "model",
}


def logical_to_pspec(axes: Sequence[Optional[str]],
                     rules: Mapping[str, Optional[str]]) -> P:
    return P(*[None if a is None else rules.get(a, None) for a in axes])


def spec_tree_to_pspecs(spec_tree, rules):
    return jax.tree.map(
        lambda axes: logical_to_pspec(axes, rules),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def shardings_for(spec_tree, rules, mesh: Mesh):
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                        spec_tree_to_pspecs(spec_tree, rules),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# param declaration helpers
# ---------------------------------------------------------------------------

class ParamTree:
    """Accumulates (params, logical-spec) pairs with a shared RNG stream."""

    def __init__(self, key: jax.Array, dtype=jnp.float32):
        self._key = key
        self.dtype = dtype
        self.params: dict[str, Any] = {}
        self.specs: dict[str, Any] = {}

    def _next(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def dense(self, name: str, shape: Sequence[int],
              axes: Sequence[Optional[str]], *, fan_in: Optional[int] = None,
              scale: float = 1.0) -> None:
        """Truncated-normal init with 1/sqrt(fan_in) scaling."""
        shape = tuple(shape)
        if fan_in is None:
            fan_in = shape[0] if shape else 1
        std = scale / math.sqrt(max(fan_in, 1))
        self.params[name] = (
            jax.random.truncated_normal(self._next(), -2.0, 2.0, shape,
                                        jnp.float32) * std).astype(self.dtype)
        self.specs[name] = tuple(axes)

    def const(self, name: str, shape: Sequence[int],
              axes: Sequence[Optional[str]], value: float = 0.0) -> None:
        self.params[name] = jnp.full(tuple(shape), value, dtype=self.dtype)
        self.specs[name] = tuple(axes)

    def custom(self, name: str, value: jax.Array,
               axes: Sequence[Optional[str]]) -> None:
        self.params[name] = value.astype(self.dtype)
        self.specs[name] = tuple(axes)

    def sub(self, name: str, other: "ParamTree") -> None:
        self.params[name] = other.params
        self.specs[name] = other.specs

    def child(self) -> "ParamTree":
        return ParamTree(self._next(), self.dtype)


def stack_specs(specs: dict) -> dict:
    """One layer's logical-spec tree with the leading 'layer' axis of
    scan-ready stacked params."""
    return jax.tree.map(
        lambda axes: ("layer", *axes), specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    """RMSNorm; ``plus_one`` uses the gemma convention (scale = 1 + w)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:
        w = 1.0 + w
    return (x * w).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               *, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (standard "half rotation", interleaved, and partial/2d variants)
# ---------------------------------------------------------------------------

def rope_cos_sin(positions: jax.Array, rot_dim: int, *,
                 base: float = 10000.0) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables of shape (*positions.shape, rot_dim // 2), f32."""
    inv = 1.0 / (base ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                          / rot_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, *,
               mode: str = "half") -> jax.Array:
    """Rotate the leading ``2 * cos.shape[-1]`` dims of the head axis.

    x: (..., S, H, D) with cos/sin (..., S, R/2) broadcast over H.
    mode 'half'        : (x1, x2) = split-in-half pairing (llama/neox)
    mode 'interleaved' : (x[0::2], x[1::2]) pairing (GPT-J / chatglm 2d rope,
                         which additionally rotates only D/2 of the head dim —
                         achieved by passing rot_dim = D // 2).
    """
    r2 = cos.shape[-1]
    rot, rest = x[..., : 2 * r2], x[..., 2 * r2:]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    if mode == "half":
        x1, x2 = rot[..., :r2], rot[..., r2:]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        out = jnp.concatenate([o1, o2], axis=-1)
    elif mode == "interleaved":
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
    else:
        raise ValueError(f"unknown rope mode {mode!r}")
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1) \
        if rest.shape[-1] else out.astype(x.dtype)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op outside jit-with-mesh."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
