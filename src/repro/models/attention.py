"""GQA attention: chunked-flash training/prefill + cached decode.

Three execution paths:

* ``xla_flash`` — pure-XLA online-softmax attention, double ``lax.scan``
  over (q-chunks, k-chunks).  This is what the multi-pod dry-run lowers
  (Pallas doesn't compile on the host platform); the inner body is
  ``jax.checkpoint``-ed so the 4k training backward stores O(S) not O(S^2).
  Sliding-window attention takes a dynamic-slice fast path: each q-chunk
  only ever touches ``window + q_chunk`` keys, making SWA prefill O(S*w).
* ``repro.kernels.flash_attn`` — the Pallas TPU kernels (forward, dK/dV,
  dQ) under one ``custom_vjp``: what ``impl='pallas'`` trains and prefills
  with on one TPU device (same math, tested equivalent; see ``attend``).
* ``decode_attend`` — one-token GQA attention against a (possibly ring)
  KV cache: a masked einsum, O(cache) per step.

Layout convention: activations (batch, seq, d_model); caches
(batch, kv_heads, cache_len, head_dim); decode positions are a scalar step
count (lockstep batch decoding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import current_mesh, shard
from repro.kernels.flash_attn.kernel import flash_attn_pallas
from repro.kernels.gram.ops import on_tpu
from repro.models import layers
from repro.models.config import ModelConfig

NEG = -1e30


# ---------------------------------------------------------------------------
# chunked flash attention in pure XLA
# ---------------------------------------------------------------------------

def _chunk_attend(q, k, v, row0, col0, *, causal, window, scale):
    """One (q-chunk, k-chunk) tile. q: (B,KV,G,qc,D), k/v: (B,KV,kc,D).
    Returns unnormalized (acc, m, l) contributions."""
    qc, kc = q.shape[3], k.shape[2]
    s = jnp.einsum("bkgqd,bksd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 4)
    mask = jnp.ones(s.shape, bool)
    if causal:
        mask &= col <= row
    if window is not None:
        mask &= col > row - window
    return s, mask


def xla_flash(q, k, v, *, causal=True, window=None, scale=None,
              q_chunk=512, k_chunk=1024, kv_valid=None):
    """q: (B, H, Sq, D); k/v: (B, KVH, Sk, D). Queries tail-aligned to keys.

    kv_valid: optional (Sk,) bool — extra key-slot mask (ragged caches)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    offset = Sk - Sq

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    nq = -(-Sq // q_chunk)
    qpad = nq * q_chunk - Sq
    qg = q.reshape(B, KV, G, Sq, D)
    if qpad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, qpad), (0, 0)))

    use_window_slice = (window is not None
                        and window + q_chunk < Sk - k_chunk // 2)

    def one_q_chunk(qi):
        qs = qi * q_chunk
        qtile = jax.lax.dynamic_slice_in_dim(qg, qs, q_chunk, axis=3)
        row0 = qs + offset

        if use_window_slice:
            ws = min(Sk, window + q_chunk)
            start = jnp.clip(row0 - window + 1, 0, Sk - ws)
            ktile = jax.lax.dynamic_slice_in_dim(k, start, ws, axis=2)
            vtile = jax.lax.dynamic_slice_in_dim(v, start, ws, axis=2)
            s, mask = _chunk_attend(qtile, ktile, vtile, row0, start,
                                    causal=causal, window=window, scale=scale)
            if kv_valid is not None:
                valid = jax.lax.dynamic_slice_in_dim(kv_valid, start, ws, 0)
                mask &= valid[None, None, None, None, :]
            s = jnp.where(mask, s, NEG)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m) * mask
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jnp.einsum("bkgqs,bksd->bkgqd", p, vtile.astype(jnp.float32))
            return jnp.where(l > 0, o / jnp.maximum(l, 1e-30), 0.0)

        nk = -(-Sk // k_chunk)
        kpad = nk * k_chunk - Sk
        # pad keys so chunk slicing never clamps (clamped starts would
        # mislabel columns and double-count tail keys)
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, kpad), (0, 0))) if kpad else k
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, kpad), (0, 0))) if kpad else v

        @jax.checkpoint
        def kstep(carry, ki):
            m_prev, l_prev, acc = carry
            ks = ki * k_chunk
            ktile = jax.lax.dynamic_slice_in_dim(kp, ks, k_chunk, axis=2)
            vtile = jax.lax.dynamic_slice_in_dim(vp, ks, k_chunk, axis=2)
            s, mask = _chunk_attend(qtile, ktile, vtile, row0, ks,
                                    causal=causal, window=window, scale=scale)
            col = ks + jax.lax.broadcasted_iota(jnp.int32, s.shape, 4)
            mask &= col < Sk  # k padding from ragged last chunk
            if kv_valid is not None:
                vpad = jnp.pad(kv_valid, (0, kpad)) if kpad else kv_valid
                valid = jax.lax.dynamic_slice_in_dim(vpad, ks, k_chunk, 0)
                mask &= valid[None, None, None, None, :]
            s = jnp.where(mask, s, NEG)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur) * mask
            l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bkgqs,bksd->bkgqd", p,
                                           vtile.astype(jnp.float32))
            return (m_cur, l_cur, acc), None

        m0 = jnp.full((B, KV, G, q_chunk, 1), NEG, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kstep, (m0, l0, a0), jnp.arange(nk))
        return jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)

    if nq == 1:
        out = one_q_chunk(jnp.asarray(0))[:, :, :, None]      # (B,KV,G,1,qc,D)
    else:
        out = jax.lax.map(one_q_chunk, jnp.arange(nq))        # (nq,B,KV,G,qc,D)
        out = jnp.moveaxis(out, 0, 3)                         # (B,KV,G,nq,qc,D)
    out = out.reshape(B, H, nq * q_chunk, D)[:, :, :Sq]
    return out.astype(q.dtype)


def _kernel_runs_here() -> bool:
    """The Pallas kernels run on a TPU, and only where no mesh of several
    devices is active: GSPMD cannot partition a ``pallas_call`` (that
    would take a ``shard_map`` around it)."""
    mesh = current_mesh()
    return on_tpu() and (mesh is None or mesh.size == 1)


def attend(q, k, v, *, causal=True, window=None, scale=None, impl="xla",
           kv_valid=None):
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D) -> (B, H, Sq, D).

    ``impl="pallas"`` takes the Pallas kernels where they run (one TPU
    device) and the cache has no ragged ``kv_valid`` mask; everywhere else
    (the CPU, the host dry-run, a multi-device mesh, ragged caches) it
    takes ``xla_flash``, as ``impl="xla"`` always does.
    ``impl="pallas_interpret"`` runs the kernels in the Pallas interpreter
    (tests)."""
    if kv_valid is not None or impl == "xla" or (
            impl == "pallas" and not _kernel_runs_here()):
        return xla_flash(q, k, v, causal=causal, window=window, scale=scale,
                         kv_valid=kv_valid)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown attention impl {impl!r}")
    return flash_attn_pallas(q, k, v, causal=causal, window=window,
                             scale=scale,
                             interpret=impl == "pallas_interpret")


# ---------------------------------------------------------------------------
# attention layer (params + cache)
# ---------------------------------------------------------------------------

# KV cache is a plain dict {"k": (B, KV, cache_len, hd), "v": ...} so layer
# caches stack cleanly under lax.scan.  Whether the cache is a ring buffer
# (cache_len == window < max_len) is *static* model-level information passed
# as an argument; the decode step counter is a single scalar owned by the
# model, not per-layer state.


def attn_init(key, cfg: ModelConfig):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": layers.linear_init(ks[0], d, H * hd, use_bias=cfg.use_bias,
                                 dtype=dt, axes=("embed", "qkv")),
        "wk": layers.linear_init(ks[1], d, KV * hd, use_bias=cfg.use_bias,
                                 dtype=dt, axes=("embed", "qkv")),
        "wv": layers.linear_init(ks[2], d, KV * hd, use_bias=cfg.use_bias,
                                 dtype=dt, axes=("embed", "qkv")),
        "wo": layers.linear_init(ks[3], H * hd, d, use_bias=cfg.use_bias,
                                 dtype=dt, axes=("qkv", "embed")),
    }


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cdt = jnp.dtype(cfg.compute_dtype)
    q = layers.linear(p["wq"], x, cdt).reshape(B, S, H, hd)
    k = layers.linear(p["wk"], x, cdt).reshape(B, S, KV, hd)
    v = layers.linear(p["wv"], x, cdt).reshape(B, S, KV, hd)
    if cfg.pos == "rope":
        q = layers.apply_rope(q.swapaxes(1, 2), positions[:, None, :],
                              theta=cfg.rope_theta,
                              rope_fraction=cfg.rope_fraction).swapaxes(1, 2)
        k = layers.apply_rope(k.swapaxes(1, 2), positions[:, None, :],
                              theta=cfg.rope_theta,
                              rope_fraction=cfg.rope_fraction).swapaxes(1, 2)
    # (B, heads, S, hd)
    return q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2)


def attn_apply(p, x, cfg: ModelConfig, *, positions, impl="xla"):
    """Training / prefill path.  x: (B, S, d) -> (B, S, d)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = shard(q, ("sub_batch", "heads", "seq", None))
    o = attend(q, k, v, causal=True, window=cfg.window, impl=impl)
    o = o.swapaxes(1, 2).reshape(B, S, cfg.num_heads * cfg.head_dim)
    return layers.linear(p["wo"], o, jnp.dtype(cfg.compute_dtype))


def cache_is_ring(cfg: ModelConfig, max_len: int) -> bool:
    return cfg.window is not None and cfg.window < max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Ring buffer of size window for SWA archs, else full-length cache."""
    clen = cfg.window if cache_is_ring(cfg, max_len) else max_len
    shape = (batch, cfg.num_kv_heads, clen, cfg.head_dim)
    zeros = shard(jnp.zeros(shape, dtype),
                  ("sub_batch", "kv_heads", "cache_seq", "head_dim"))
    return {"k": zeros, "v": zeros}


def attn_decode(p, x, cfg: ModelConfig, cache: dict, *, step, ring: bool):
    """One-token decode.  x: (B, 1, d); step: () int32 absolute position."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    positions = jnp.broadcast_to(step[None, None], (B, 1))
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)  # (B,*,1,hd)

    clen = cache["k"].shape[2]
    slot = jax.lax.rem(step, clen) if ring else jnp.minimum(step, clen - 1)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=2)

    idx = jnp.arange(clen)
    filled = ((idx <= step) | (step >= clen)) if ring else (idx <= step)
    qg = q.reshape(B, KV, H // KV, 1, hd)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    s = jnp.where(filled[None, None, None, None, :], s, NEG)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", w, v.astype(jnp.float32))
    o = o.reshape(B, 1, H * hd).astype(x.dtype)
    out = layers.linear(p["wo"], o, jnp.dtype(cfg.compute_dtype))
    return out, {"k": k, "v": v}
