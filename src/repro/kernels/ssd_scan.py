"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

State-space duality (arXiv:2405.21060): within a `chunk` the recurrence is
a masked attention-like dense product (MXU work); across chunks a small
state (heads, head_dim, d_state) is carried.  The kernel grid is

    (batch, head_blocks, n_chunks)

with the chunk axis innermost and *sequential*; the carried state lives in
VMEM scratch (bh * hd * ds * 4 B ~ 128 KiB for bh=4, hd=64, ds=128).

Per-program VMEM working set (chunk=256, bh=4, hd=64, ds=128, f32):
  x (256,4,64) 256K + L (4,256,256) 1 MiB + scores (256,256) 256K
  + state (4,64,128) 128K + B/C (256,128) 2*128K  ~ 2 MiB -- fits.

B/C projections are group-shared (ngroups=1) exactly as in the model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segsum_tril(dA):
    """dA: (bh, q). Returns (bh, q, q) with out[h,i,j] = sum_{j<k<=i} dA[h,k]
    on the lower triangle, -inf above."""
    bh, q = dA.shape
    cs = jnp.cumsum(dA, axis=-1)  # (bh, q)
    diff = cs[:, :, None] - cs[:, None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.where((rows >= cols)[None], diff, -jnp.inf)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)        # (q, bh, hd)
    dt = dt_ref[0].astype(jnp.float32)      # (q, bh)
    A = a_ref[...].astype(jnp.float32)      # (bh,)
    B = b_ref[0].astype(jnp.float32)        # (q, ds)
    C = c_ref[0].astype(jnp.float32)        # (q, ds)

    dA = dt * A[None, :]                    # (q, bh)
    dA_cum = jnp.cumsum(dA, axis=0)         # (q, bh)

    # intra-chunk (the "attention-like" dual form)
    L = jnp.exp(_segsum_tril(dA.T))         # (bh, q, q)
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )                                       # (q, q)
    dtx = dt[:, :, None] * x                # (q, bh, hd)
    w = L * scores[None]                    # (bh, q, q)
    y_diag = jnp.einsum("hij,jhp->ihp", w, dtx,
                        preferred_element_type=jnp.float32)

    # chunk-final state contribution
    decay_to_end = jnp.exp(dA_cum[-1:, :] - dA_cum)  # (q, bh)
    states = jnp.einsum("jn,jhp->hpn", B, decay_to_end[:, :, None] * dtx,
                        preferred_element_type=jnp.float32)  # (bh, hd, ds)

    # inter-chunk: y_off from the state entering this chunk
    prev = state_ref[...]                   # (bh, hd, ds)
    decay_in = jnp.exp(dA_cum)              # (q, bh)
    y_off = jnp.einsum("in,hpn->ihp", C, prev,
                       preferred_element_type=jnp.float32) * decay_in[:, :, None]

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)
    chunk_decay = jnp.exp(dA_cum[-1, :])    # (bh,)
    state_ref[...] = prev * chunk_decay[:, None, None] + states


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_heads", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, block_heads: int = 4,
             interpret: bool = False):
    """Chunked SSD scan (matches repro.models.ssd.ssd_chunked semantics).

    x:  (b, s, nh, hd)   conv'd + activated inputs
    dt: (b, s, nh)       softplus'd step sizes
    A:  (nh,)            negative decay rates
    B:  (b, s, ds), C: (b, s, ds)   shared projections (ngroups=1)
    Returns y: (b, s, nh, hd).
    """
    b, s, nh, hd = x.shape
    ds = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    block_heads = min(block_heads, nh)
    assert nh % block_heads == 0, (nh, block_heads)
    nc = s // chunk
    nhb = nh // block_heads

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y = pl.pallas_call(
        kernel,
        grid=(b, nhb, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_heads, hd),
                         lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, chunk, block_heads),
                         lambda b_, h_, c_: (b_, c_, h_)),
            pl.BlockSpec((block_heads,), lambda b_, h_, c_: (h_,)),
            pl.BlockSpec((1, chunk, ds), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_heads, hd),
                               lambda b_, h_, c_: (b_, c_, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, nh, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_heads, hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A, B, C)
    return y
