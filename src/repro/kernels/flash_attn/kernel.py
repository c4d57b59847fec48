"""Flash attention (online softmax) Pallas kernel — TPU target.

Grid (batch*heads, n_q_blocks, n_k_blocks); the innermost k axis revisits
the same output block, carrying the running max ``m``, normalizer ``l`` and
unnormalized accumulator in *output* VMEM blocks (constant index_map over
k) — initialized at k==0 and normalized in place at the last k step.  This
is the canonical Pallas reduction idiom and avoids backend-specific scratch.

Numerics: scores are masked with a finite sentinel (NEG = -1e30) and the
probability tile is multiplied by the boolean mask, so fully-masked blocks
contribute exactly zero without -inf/-inf NaNs.  Accumulation is fp32
regardless of input dtype; the MXU contractions use
preferred_element_type=float32.

Supports causal masking and sliding windows (the serving path of the SWA
variants); queries are aligned to the *tail* of the key sequence so the same
kernel serves prefill (sq == sk) and decode (sq == 1, sk == cache length).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  scale, causal, window, block_q, block_k, seq_q, seq_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (block_q, d)
    k = k_ref[0].astype(jnp.float32)                    # (block_k, d)
    v = v_ref[0].astype(jnp.float32)                    # (block_k, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + (seq_k - seq_q)                               # absolute q position
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = col < seq_k                                  # k-padding
    mask &= row < seq_k                                 # q-padding (tail align)
    if causal:
        mask &= col <= row
    if window is not None:
        mask &= col > row - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[0]                                   # (block_q, 1)
    l_prev = l_ref[0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)                     # <= 1, finite
    p = jnp.exp(s - m_cur) * mask.astype(jnp.float32)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = o_ref[0].astype(jnp.float32) * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_ref[0] = m_cur
    l_ref[0] = l_new
    o_ref[0] = acc.astype(o_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[0]
        o_ref[0] = jnp.where(
            l > 0, o_ref[0].astype(jnp.float32) / jnp.maximum(l, 1e-30), 0.0
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attn_pallas(q, k, v, *, causal: bool = True,
                      window: int | None = None, scale: float | None = None,
                      block_q: int = 128, block_k: int = 128,
                      interpret: bool = False):
    """q: (b, h, sq, d), k/v: (b, h, sk, d) -> (b, h, sq, d)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(8, sk))
    sq_pad = -(-sq // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k

    def pad(x, s_pad):
        return jnp.zeros((b * h, s_pad, d), x.dtype).at[:, :x.shape[2], :].set(
            x.reshape(b * h, x.shape[2], d))

    qp, kp, vp = pad(q, sq_pad), pad(k, sk_pad), pad(v, sk_pad)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_q=sq, seq_k=sk)

    o, _, _ = pl.pallas_call(
        kernel,
        grid=(b * h, sq_pad // block_q, sk_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            # The o block is a cross-step accumulator (the k axis revisits
            # it): it must be fp32 even for bf16 inputs, else every store
            # rounds the running sum (KPRECISION).  Cast once on the way out.
            jax.ShapeDtypeStruct((b * h, sq_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :sq, :].reshape(b, h, sq, d).astype(q.dtype)
