"""Flash attention (online softmax) as Pallas TPU kernels, with its own backward.

Layout: q (B, H, Sq, D); k, v (B, KV, Sk, D) with H = G * KV (grouped-query
attention).  Query head h reads K/V head h // G through the BlockSpecs'
index maps, so K/V are never repeated in HBM.  Queries are aligned to the
*tail* of the keys (query i sits at absolute position i + Sk - Sq), so the
same kernels serve training and prefill (Sq == Sk) and decode (Sq == 1).

Three kernels, each named so that the profiler shows it by name:

  ``flash_attn_fwd``      grid (B, H, q blocks, kv blocks): o, and one fp32
                          log-sum-exp (lse) per query row;
  ``flash_attn_bwd_dkv``  grid (B, KV, kv blocks, G, q blocks): dK and dV,
                          summed in VMEM over the G query heads of a group;
  ``flash_attn_bwd_dq``   grid (B, H, q blocks, kv blocks): dQ.

:func:`flash_attn_pallas` is a ``jax.custom_vjp`` over them.  Its residuals
are q, k, v, o and lse — O(S) per head, never the (S, S) scores; the
backward computes ``di = sum(o * do)`` once, in XLA, and hands it to both
backward kernels, which rebuild each probability tile from q, k and lse.
Under a ``jax.checkpoint`` the forward kernel runs once more in the
backward pass, and nothing inside it is checkpointed again.

Every score tile is held transposed, (kv rows, q rows): queries lie along
the lanes, so the running max and normaliser, lse and di are lane-dense
rows (lse and di are stored (B, H, 1, S)) and the softmax reductions run
over sublanes.  The forward accumulates o^T and the dQ kernel dQ^T, each
transposed once when its block is written.

Numerics: the MXU operands are in the inputs' dtype — bf16 q, k and v as
the model makes them, and the probability and dS tiles cast to that dtype
for the PV, dV, dK and dQ dots.  Every dot accumulates in fp32
(``preferred_element_type``); the running max, normaliser, accumulators,
lse and di are fp32.  This is the arithmetic of the XLA path on the TPU,
whose default-precision dots also feed bf16-valued operands to the MXU and
accumulate in fp32: it is not a lower precision.  Scores are masked with
the finite sentinel NEG and the probability tile is zeroed where masked,
so a fully masked row gives o = 0 and no -inf - -inf NaN.

Blocks: a block lying wholly above the causal diagonal, or wholly outside
the sliding window, is skipped with ``pl.when``, and the K/V (forward, dQ)
or Q (dKV) index map is clamped to the blocks a row block needs, so a
skipped step names the block already in VMEM and issues no DMA.  Only a
block that crosses the diagonal, the window's edge or the sequence padding
builds a mask.  A block is ``BLOCK`` rows, or the whole sequence where
that is shorter; a sequence that is not a multiple of its block is
zero-padded, and one that is goes in without a copy.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
NT = (((1,), (1,)), ((), ()))            # a @ b.T
TN = (((0,), (0,)), ((), ()))            # a.T @ b
F32 = jnp.float32

# Block target, in rows, of q and of k/v in all three kernels: the fastest
# of 256, 512 and 1024 for each kernel on a TPU v5e at (4, 15 heads, 2048,
# 64) bf16 (PERF.md).
BLOCK = 512


def _block(n: int, target: int | None) -> int:
    """The target, or the whole length (to a bf16 sublane tile) if shorter."""
    return min(target or BLOCK, -(-n // 16) * 16)


@dataclasses.dataclass(frozen=True)
class _Spec:
    causal: bool
    window: int | None
    scale: float
    seq_q: int                 # unpadded lengths: the mask's bounds
    seq_k: int
    bq: int                    # block rows of q and of k/v
    bk: int
    padded_q: bool             # the inputs carry padding rows
    padded_k: bool
    interpret: bool


# ---------------------------------------------------------------------------
# which blocks run, and which build a mask
# ---------------------------------------------------------------------------

def _and(*conds):
    """Conjunction of Python and traced booleans; Python True if empty."""
    out = True
    for c in conds:
        if c is True:
            continue
        if c is False:
            return False
        out = c if out is True else out & c
    return out


def _when(cond, fn):
    if cond is True:
        fn()
    elif cond is not False:
        pl.when(cond)(fn)


def _origin(i, j, spec):
    """Absolute position of block (i, j)'s first query row and first key."""
    return i * spec.bq + (spec.seq_k - spec.seq_q), j * spec.bk


def _visible(r0, c0, spec):
    """Some entry of the block is attended to."""
    return _and(c0 <= r0 + spec.bq - 1 if spec.causal else True,
                c0 + spec.bk - 1 > r0 - spec.window if spec.window else True)


def _whole(r0, c0, i, spec):
    """Every entry of the block is attended to: no mask needed."""
    bq, bk = spec.bq, spec.bk
    return _and(c0 + bk - 1 <= r0 if spec.causal else True,
                c0 > r0 + bq - 1 - spec.window if spec.window else True,
                (i + 1) * bq <= spec.seq_q if spec.padded_q else True,
                c0 + bk <= spec.seq_k if spec.padded_k else True)


def _mask(shape, r0, c0, spec):
    """Boolean mask of a (kv, q) score tile."""
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    mask = (col < spec.seq_k) & (row < spec.seq_k)   # k and q padding
    if spec.causal:
        mask &= col <= row
    if spec.window:
        mask &= col > row - spec.window
    return mask


def _run(update, i, j, spec):
    """``update(masked)`` on block (i, j) if it is visible, masked only
    where needed."""
    r0, c0 = _origin(i, j, spec)
    visible = _visible(r0, c0, spec)
    whole = _whole(r0, c0, i, spec)
    if whole is True or whole is False:
        _when(visible, functools.partial(update, not whole))
        return
    _when(_and(visible, jnp.logical_not(whole)),
          functools.partial(update, True))
    _when(_and(visible, whole), functools.partial(update, False))


def _kv_span(i, nk, spec):
    """First and last kv block that q block i attends to."""
    r0 = _origin(i, 0, spec)[0]
    bq, bk = spec.bq, spec.bk
    lo = (jnp.clip((r0 - spec.window + 1) // bk, 0, nk - 1)
          if spec.window else 0)
    hi = jnp.clip((r0 + bq - 1) // bk, 0, nk - 1) if spec.causal else nk - 1
    return lo, hi


def _q_span(j, nq, spec):
    """First and last q block that attends to kv block j."""
    off, bq, bk = spec.seq_k - spec.seq_q, spec.bq, spec.bk
    lo = jnp.clip((j * bk - off) // bq, 0, nq - 1) if spec.causal else 0
    hi = (jnp.clip((j * bk + bk - 1 + spec.window - 1 - off) // bq, 0, nq - 1)
          if spec.window else nq - 1)
    return lo, hi


def _vmem(shape):
    return pltpu.VMEM(shape, F32)


def _clamp(x, span):
    lo, hi = span
    return jnp.minimum(jnp.maximum(x, lo), hi)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                spec):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    r0, c0 = _origin(i, j, spec)

    def update(masked):
        v = v_ref[0, 0]
        s_t = jax.lax.dot_general(k_ref[0, 0], q_ref[0, 0], NT,
                                  preferred_element_type=F32) * spec.scale
        if masked:
            mask = _mask(s_t.shape, r0, c0, spec)
            s_t = jnp.where(mask, s_t, NEG)
        m_prev = m_sc[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p_t = jnp.exp(s_t - m_cur)
        if masked:
            p_t = jnp.where(mask, p_t, 0.0)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p_t, axis=0, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            v, p_t.astype(v.dtype), TN, preferred_element_type=F32)
        m_sc[...] = m_cur

    _run(update, i, j, spec)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_sc[...]
        seen = l > 0
        safe = jnp.where(seen, l, 1.0)
        o_ref[0, 0] = jnp.where(seen, acc_sc[...] / safe,
                                0.0).T.astype(o_ref.dtype)
        # a row that sees no key gets lse = -NEG: exp(s - lse) is then 0
        lse_ref[0, 0] = jnp.where(seen, m_sc[...] + jnp.log(safe), -NEG)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, spec):
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    r0, c0 = _origin(i, j, spec)

    def update(masked):
        q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
        s_t = jax.lax.dot_general(k, q, NT,
                                  preferred_element_type=F32) * spec.scale
        p_t = jnp.exp(s_t - lse_ref[0, 0])
        if masked:
            p_t = jnp.where(_mask(s_t.shape, r0, c0, spec), p_t, 0.0)
        dv_sc[...] += jnp.dot(p_t.astype(do.dtype), do,
                              preferred_element_type=F32)
        dp_t = jax.lax.dot_general(v_ref[0, 0], do, NT,
                                   preferred_element_type=F32)
        ds_t = p_t * (dp_t - di_ref[0, 0])
        dk_sc[...] += jnp.dot(ds_t.astype(q.dtype), q,
                              preferred_element_type=F32)

    _run(update, i, j, spec)

    @pl.when((g == pl.num_programs(3) - 1) & (i == pl.num_programs(4) - 1))
    def _finalize():
        dk_ref[0, 0] = (dk_sc[...] * spec.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc, *,
               spec):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    r0, c0 = _origin(i, j, spec)

    def update(masked):
        k = k_ref[0, 0]
        s_t = jax.lax.dot_general(k, q_ref[0, 0], NT,
                                  preferred_element_type=F32) * spec.scale
        p_t = jnp.exp(s_t - lse_ref[0, 0])
        if masked:
            p_t = jnp.where(_mask(s_t.shape, r0, c0, spec), p_t, 0.0)
        dp_t = jax.lax.dot_general(v_ref[0, 0], do_ref[0, 0], NT,
                                   preferred_element_type=F32)
        ds_t = p_t * (dp_t - di_ref[0, 0])
        dq_sc[...] += jax.lax.dot_general(k, ds_t.astype(k.dtype), TN,
                                          preferred_element_type=F32)

    _run(update, i, j, spec)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_sc[...] * spec.scale).T.astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_calls
# ---------------------------------------------------------------------------

def _fwd(q, k, v, spec):
    """o (B, H, Sq, D) and lse (B, H, 1, Sq) fp32, on padded inputs."""
    B, H, Sq, D = q.shape
    G, Sk = H // k.shape[1], k.shape[2]
    bq, bk = spec.bq, spec.bk
    nk = Sk // bk

    def kv_map(b, h, i, j):
        return b, h // G, _clamp(j, _kv_span(i, nk, spec)), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec),
        grid=(B, H, Sq // bq, nk),
        in_specs=[pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map)],
        out_specs=[pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
                   pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), F32)],
        scratch_shapes=[_vmem((1, bq)), _vmem((1, bq)), _vmem((D, bq))],
        interpret=spec.interpret,
        name="flash_attn_fwd",
    )(q, k, v)


def _bwd_dkv(q, k, v, do, lse, di, spec):
    """dK, dV (B, KV, Sk, D); lse and di are (B, H, 1, Sq)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = spec.bq, spec.bk
    nq = Sq // bq

    def q_map(b, kv, j, g, i):
        return b, kv * G + g, _clamp(i, _q_span(j, nq, spec)), 0

    def row_map(b, kv, j, g, i):
        return b, kv * G + g, 0, _clamp(i, _q_span(j, nq, spec))

    def kv_map(b, kv, j, g, i):
        return b, kv, j, 0

    return pl.pallas_call(
        functools.partial(_dkv_kernel, spec=spec),
        grid=(B, KV, Sk // bk, G, nq),
        in_specs=[pl.BlockSpec((1, 1, bq, D), q_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bq, D), q_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map)],
        out_specs=[pl.BlockSpec((1, 1, bk, D), kv_map),
                   pl.BlockSpec((1, 1, bk, D), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[_vmem((bk, D)), _vmem((bk, D))],
        interpret=spec.interpret,
        name="flash_attn_bwd_dkv",
    )(q, k, v, do, lse, di)


def _bwd_dq(q, k, v, do, lse, di, spec):
    """dQ (B, H, Sq, D); lse and di are (B, H, 1, Sq)."""
    B, H, Sq, D = q.shape
    G, Sk = H // k.shape[1], k.shape[2]
    bq, bk = spec.bq, spec.bk
    nk = Sk // bk

    def q_map(b, h, i, j):
        return b, h, i, 0

    def row_map(b, h, i, j):
        return b, h, 0, i

    def kv_map(b, h, i, j):
        return b, h // G, _clamp(j, _kv_span(i, nk, spec)), 0

    return pl.pallas_call(
        functools.partial(_dq_kernel, spec=spec),
        grid=(B, H, Sq // bq, nk),
        in_specs=[pl.BlockSpec((1, 1, bq, D), q_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bq, D), q_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map)],
        out_specs=pl.BlockSpec((1, 1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_vmem((D, bq))],
        interpret=spec.interpret,
        name="flash_attn_bwd_dq",
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, spec):
    return _fwd(q, k, v, spec)[0]


def _attention_fwd(q, k, v, spec):
    o, lse = _fwd(q, k, v, spec)
    return o, (q, k, v, o, lse)


def _attention_bwd(spec, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1)[:, :, None, :]
    dk, dv = _bwd_dkv(q, k, v, do, lse, di, spec)
    dq = _bwd_dq(q, k, v, do, lse, di, spec)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


def _pad(x, n):
    if x.shape[2] == n:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attn_pallas(q, k, v, *, causal: bool = True,
                      window: int | None = None, scale: float | None = None,
                      block_q: int | None = None, block_k: int | None = None,
                      interpret: bool = False):
    """Differentiable attention.  q: (b, h, sq, d), k/v: (b, kv, sk, d) with
    h a multiple of kv -> (b, h, sq, d) in q's dtype.

    ``block_q``/``block_k`` replace the block target (tests use small
    blocks to reach many-block paths at small S).
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if h % kvh:
        raise ValueError(f"{h} query heads are not a multiple of {kvh} "
                         "K/V heads")
    bq, bk = _block(sq, block_q), _block(sk, block_k)
    sq_pad, sk_pad = -(-sq // bq) * bq, -(-sk // bk) * bk
    spec = _Spec(causal=causal, window=window,
                 scale=float(scale if scale is not None else d ** -0.5),
                 seq_q=sq, seq_k=sk, bq=bq, bk=bk, padded_q=sq_pad != sq,
                 padded_k=sk_pad != sk, interpret=interpret)
    o = _attention(_pad(q, sq_pad), _pad(k, sk_pad), _pad(v, sk_pad), spec)
    return o[:, :, :sq] if sq_pad != sq else o
