"""Shared neural-net layers: norms, RoPE, blocked attention, MLPs.

Everything is pure JAX (no flax).  Parameters are plain dict pytrees built
from `ParamSpec`s so that shape/dtype/logical-axis metadata exists without
allocating memory (the dry-run only ever sees `jax.ShapeDtypeStruct`s).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape + dtype + logical axis names + init."""

    shape: tuple
    axes: tuple  # logical axis name per dim (or None)
    dtype: jnp.dtype = jnp.float32
    init: str = "normal"  # "normal" | "zeros" | "ones" | "conv"
    scale: float = 0.02

    def initialize(self, key) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        fan_in = self.shape[0] if len(self.shape) > 1 else max(self.shape[0], 1)
        scale = self.scale if self.init == "normal" else 1.0 / np.sqrt(fan_in)
        return (scale * jax.random.normal(key, self.shape)).astype(self.dtype)


def init_tree(specs, key):
    """Initialize a pytree of ParamSpec -> pytree of arrays."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    arrs = [s.initialize(k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrs)


def shapes_tree(specs):
    """Pytree of ParamSpec -> pytree of ShapeDtypeStruct (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ---------------------------------------------------------------------------
# Sharding helper
# ---------------------------------------------------------------------------


def shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint if inside a mesh context, else identity."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)

    def ok(entry) -> bool:
        if entry is None:
            return True
        if isinstance(entry, (tuple, list)):
            return all(e in names for e in entry)
        return entry in names

    def fits(entry, dim) -> bool:
        if entry is None:
            return True
        sz = 1
        for e in entry if isinstance(entry, (tuple, list)) else (entry,):
            sz *= mesh.shape[e]
        return dim % sz == 0

    clean = tuple(
        e if ok(e) and fits(e, d) else None for e, d in zip(spec, x.shape)
    )
    return jax.lax.with_sharding_constraint(x, P(*clean))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x, scale, bias, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (S, d_model)."""
    pos = np.arange(seq)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    inv = 1.0 / (10_000 ** (dim / max(d_model // 2 - 1, 1)))
    ang = pos * inv
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32
    )


# ---------------------------------------------------------------------------
# Blocked (flash-style) attention -- pure JAX, bounded working set.
#
# The Pallas kernel in repro.kernels.flash_attention is the TPU fast path;
# this is the XLA-lowerable equivalent used by the dry-run and CPU tests.
# Causal masking is applied per KV block; with `window>0` (SWA) the KV range
# is structurally sliced so long-context cost is O(S * window).
# ---------------------------------------------------------------------------


def _attn_one_q_block(q, k, v, q_pos, k_pos, causal, window, scale):
    """q: (B,bq,H,D) k/v: (B,Sk,Hkv,D). Returns (B,bq,H,D). Flops: full."""
    b, bq, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.reshape(b, bq, hkv, rep, d).reshape(b, bq, hkv * rep, d),
        jnp.repeat(k, rep, axis=2),
        preferred_element_type=jnp.float32,
    )
    scores = scores * scale
    mask = jnp.ones((bq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, rep, axis=2))
    return out


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset=0,
    k_offset=0,
    block_q: int = 1024,
) -> jax.Array:
    """Memory-bounded attention.

    q: (B, Sq, Hq, D);  k, v: (B, Sk, Hkv, D)  (GQA: Hq % Hkv == 0).
    q_offset / k_offset: absolute position of q[.,0]/k[.,0] (int or traced).

    For SWA (window > 0) each q block structurally slices only the
    (window + block_q) KV positions it can see -> O(S*W) not O(S^2).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    q_offset = jnp.asarray(q_offset, jnp.int32)
    k_offset = jnp.asarray(k_offset, jnp.int32)

    if sq <= block_q or sq % block_q != 0:
        # single-block fallback (short or non-multiple sequences, e.g. the
        # whisper encoder's 1500); the Pallas kernel handles padding on TPU.
        q_pos = q_offset + jnp.arange(sq, dtype=jnp.int32)
        k_pos = k_offset + jnp.arange(sk, dtype=jnp.int32)
        return _attn_one_q_block(q, k, v, q_pos, k_pos, causal, window, scale)

    nb = sq // block_q
    qb = q.reshape(b, nb, block_q, h, d).transpose(1, 0, 2, 3, 4)

    use_slice = window > 0 and sk > 2 * (window + block_q)
    if use_slice:
        # KV slice length: window + block ahead of it, padded to block_q.
        slice_len = int(
            np.ceil((window + block_q) / block_q) * block_q
        )

    def body(carry, xs):
        del carry
        qi, i = xs
        q_pos = q_offset + i * block_q + jnp.arange(block_q, dtype=jnp.int32)
        if use_slice:
            start = jnp.clip(
                q_offset + i * block_q + block_q - slice_len - k_offset,
                0,
                sk - slice_len,
            )
            ki = jax.lax.dynamic_slice_in_dim(k, start, slice_len, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, slice_len, axis=1)
            k_pos = k_offset + start + jnp.arange(slice_len, dtype=jnp.int32)
        else:
            ki, vi = k, v
            k_pos = k_offset + jnp.arange(sk, dtype=jnp.int32)
        out = _attn_one_q_block(qi, ki, vi, q_pos, k_pos, causal, window, scale)
        return None, out

    _, outs = jax.lax.scan(
        body, None, (qb, jnp.arange(nb, dtype=jnp.int32))
    )
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, Hq, D); k_cache/v_cache: (B, S, Hkv, D); cache_len: (B,) int32 --
    number of valid entries.  For ring buffers (window>0) the cache stores the
    last `S` tokens in wrap-around order and all S slots are attended with an
    age mask.
    """
    b, s, hkv, d = k_cache.shape
    h = q.shape[1]
    rep = h // hkv
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum(
        "bhd,bkhd->bhk",
        q,
        jnp.repeat(k_cache, rep, axis=2),
        preferred_element_type=jnp.float32,
    ) * scale
    idx = jnp.arange(s, dtype=jnp.int32)[None, :]  # (1, S)
    valid = idx < cache_len[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bhk,bkhd->bhd", probs, jnp.repeat(v_cache, rep, axis=2))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def gated_mlp(x, wi, wg, wo, tp_axis="model"):
    """SwiGLU: silu(x@wg) * (x@wi) @ wo."""
    h = jnp.einsum("...d,df->...f", x, wi)
    g = jnp.einsum("...d,df->...f", x, wg)
    h = jax.nn.silu(g) * h
    return jnp.einsum("...f,fd->...d", h, wo)


def gelu_mlp(x, w1, b1, w2, b2):
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, w1) + b1, approximate=True)
    return jnp.einsum("...f,fd->...d", h, w2) + b2
