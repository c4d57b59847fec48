"""Coordinate-wise robust statistics over the worker axis, streamed over n.

These are the O(n*p) memory-bound inner loops of the coordinate-wise
baseline aggregators (median / trimmed-mean / MeaMed / Phocas).  The sort
that dominates them runs over the *worker* axis, which is tiny (p <= 64) and
static — so instead of ``lax.sort`` (unsupported inside Pallas TPU kernels,
and scalar-comparator-slow on XLA:CPU) we unroll an **odd-even transposition
sorting network**: p rounds of vectorized compare-exchange on
(p, block_n) VMEM tiles.  Each compare-exchange is a min/max pair on full
lanes, i.e. pure VPU work, and the network depth is p — for p = 16..64 the
kernel stays comfortably memory-bound, which is the roofline-optimal regime
for these ops.

The coordinate stream is chunked with the *same* static plan the fused tree
Gram uses (:func:`repro.kernels.gram.ref.chunk_schedule`, stride 1 — order
statistics must see every coordinate), so the two production kernels share
one grid/padding convention.

Key-value variants (MeaMed/Phocas need "k values nearest a center") carry
the payload through the network with ``where`` on the swap predicate; the
strict ``>`` swap keeps the network stable, matching ``jnp.argsort``'s
stable tie-breaking in the oracles.

**Masked variants** take a (p,) active-worker membership mask (the
:mod:`repro.dist.membership` convention): inactive rows are pushed to the
+sentinel before the network, so they sort to the top and every order
statistic is computed at *traced* positions derived from the active count
W_a = sum(mask) — dynamic membership never changes a shape, so the same
compiled kernel serves every subset.  Row selection at a traced index is a
broadcasted-iota compare + masked row-sum (no dynamic gather on the
sublane axis).

Worker-axis padding: p is padded to the fp32 sublane multiple (8) with
sentinel keys, which sort to the top and are never touched by the
statistics (unmasked: all indices < p; masked: pad rows carry mask 0).

Two (W, W)-sized *distance-selection* kernels live here too:
:func:`krum_scores_pallas` (sum of the k smallest off-diagonal distances
per worker) and :func:`bulyan_select_pallas` (Bulyan's theta-round
recursive Multi-Krum selection, all rounds fused into one kernel via a
``fori_loop`` carrying the availability mask in VMEM — one dispatch
instead of theta sorts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gram.ref import chunk_schedule

# Sentinel pushed into padded / inactive rows.  finfo.max rather than inf so
# |sentinel - center| stays well-ordered even when the center itself is
# garbage (all-inactive columns), and mirrors the pre-streaming kernel.
_SENTINEL = float(jnp.finfo(jnp.float32).max)


def _pair_roles(shape, start: int):
    """(left, right) row-role masks for one odd-even round.

    Round parity ``start`` pairs rows (i, i+1) for i in
    range(start, P - 1, 2); ``left`` marks the lower row of each pair,
    ``right`` the upper.  Whole-array masks keep each round a handful of
    vector ops — a per-element ``.at[i].set`` formulation traces O(P^2)
    dynamic-update-slices and takes XLA minutes to compile at P = 64.
    """
    P = shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    left = ((row - start) % 2 == 0) & (row >= start) & (row < P - 1)
    right = ((row - start) % 2 == 1) & (row >= start + 1)
    return left, right


def _one_round(x: jnp.ndarray, start: int) -> jnp.ndarray:
    """One fully-vectorized compare-exchange round: every row sees both
    neighbours via roll, then keeps min/max according to its pair role
    (the wrapped neighbour is never selected — the role masks exclude the
    edge rows)."""
    left, right = _pair_roles(x.shape, start)
    up = jnp.roll(x, -1, axis=0)           # row i sees x[i + 1]
    down = jnp.roll(x, 1, axis=0)          # row i sees x[i - 1]
    return jnp.where(left, jnp.minimum(x, up),
                     jnp.where(right, jnp.maximum(x, down), x))


def _sort_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Odd-even transposition sort along axis 0 (ascending).

    P rounds total, rolled into a ``fori_loop`` over (even, odd) round
    pairs so the traced program stays constant-size in P (P is always
    even here — padded to the sublane multiple).
    """
    P = x.shape[0]
    return jax.lax.fori_loop(
        0, P // 2, lambda _, y: _one_round(_one_round(y, 0), 1), x)


def _kv_round(k: jnp.ndarray, v: jnp.ndarray, start: int):
    left, right = _pair_roles(k.shape, start)
    ku, kd = jnp.roll(k, -1, axis=0), jnp.roll(k, 1, axis=0)
    vu, vd = jnp.roll(v, -1, axis=0), jnp.roll(v, 1, axis=0)
    swap_l = left & (k > ku)               # lower row takes the pair min
    swap_r = right & (kd > k)              # upper row takes the pair max
    return (jnp.where(swap_l, ku, jnp.where(swap_r, kd, k)),
            jnp.where(swap_l, vu, jnp.where(swap_r, vd, v)))


def _sort_rows_kv(k: jnp.ndarray, v: jnp.ndarray):
    """Sort rows of k ascending, permuting payload v identically (stable:
    strict-``>`` swaps preserve worker order on ties, like jnp.argsort)."""
    P = k.shape[0]

    def pair(_, kv):
        kv = _kv_round(*kv, 0)
        return _kv_round(*kv, 1)

    return jax.lax.fori_loop(0, P // 2, pair, (k, v))


def _median_from_sorted(s: jnp.ndarray, p: int) -> jnp.ndarray:
    if p % 2 == 1:
        return s[(p - 1) // 2]
    return 0.5 * (s[p // 2 - 1] + s[p // 2])


def _row_at(s: jnp.ndarray, idx) -> jnp.ndarray:
    """s[idx] for a *traced* row index: iota compare + masked row-sum."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    return jnp.sum(jnp.where(rows == idx, s, 0.0), axis=0)


# ---------------------------------------------------------------------------
# coordinate-stat kernels (grid streams over n)
# ---------------------------------------------------------------------------

def _make_kernel(op: str, p: int, f: int):
    """Unmasked kernel body: static p, statically clamped f."""
    kt = min(f, (p - 1) // 2)                  # trim width (both sides)
    ka = max(p - f, 1)                         # "k nearest center" count

    def kernel(g_ref, out_ref):
        g = g_ref[...].astype(jnp.float32)        # (p_pad, block_n)
        s = _sort_rows(g)
        if op == "median":
            r = _median_from_sorted(s, p)
        elif op == "trimmed_mean":
            r = jnp.mean(s[kt:p - kt], axis=0)
        elif op in ("meamed", "phocas"):
            if op == "meamed":
                center = _median_from_sorted(s, p)
            else:
                center = jnp.mean(s[kt:p - kt], axis=0)
            dist = jnp.abs(g - center[None, :])    # sentinel rows stay huge
            _, vals = _sort_rows_kv(dist, g)
            r = jnp.mean(vals[:ka], axis=0)
        else:
            raise ValueError(op)
        out_ref[...] = r[None, :].astype(out_ref.dtype)
    return kernel


def _make_masked_kernel(op: str, p: int, f: int):
    """Masked kernel body: order statistics at traced positions.

    Mirrors the ``masked_*`` functions in :mod:`repro.core.aggregators`
    exactly: W_a = max(sum(mask), 1) is traced, inactive rows carry the
    sentinel, and every index/count derives from W_a so the same compiled
    kernel serves every membership subset.
    """

    def kernel(g_ref, m_ref, out_ref):
        g = g_ref[...].astype(jnp.float32)        # (p_pad, block_n)
        m = m_ref[...].astype(jnp.float32)        # (p_pad, 1)
        active = m > 0.0                          # pad rows carry mask 0
        wa = jnp.maximum(jnp.sum(m.astype(jnp.int32)), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        s = _sort_rows(jnp.where(active, g, _SENTINEL))

        def masked_median():
            return 0.5 * (_row_at(s, (wa - 1) // 2) + _row_at(s, wa // 2))

        def masked_trimmed():
            kt = jnp.minimum(f, (wa - 1) // 2)
            sel = (rows >= kt) & (rows < wa - kt)
            return (jnp.sum(jnp.where(sel, s, 0.0), axis=0)
                    / jnp.maximum(wa - 2 * kt, 1).astype(jnp.float32))

        if op == "median":
            r = masked_median()
        elif op == "trimmed_mean":
            r = masked_trimmed()
        elif op in ("meamed", "phocas"):
            center = masked_median() if op == "meamed" else masked_trimmed()
            dist = jnp.where(active, jnp.abs(g - center[None, :]), _SENTINEL)
            _, vals = _sort_rows_kv(dist, g)
            ka = jnp.maximum(wa - f, 1)
            r = (jnp.sum(jnp.where(rows < ka, vals, 0.0), axis=0)
                 / ka.astype(jnp.float32))
        else:
            raise ValueError(op)
        out_ref[...] = r[None, :].astype(out_ref.dtype)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("op", "f", "block_n", "interpret"))
def coord_stats_pallas(Gw: jnp.ndarray, mask: jnp.ndarray | None = None, *,
                       op: str, f: int = 1, block_n: int = 2048,
                       interpret: bool = False):
    """Coordinate-wise robust stat over workers.  Gw: (p, n) -> (n,) fp32.

    Args:
      Gw: worker-major (p, n) gradient matrix (fp32 or bf16; the kernel
        upcasts tiles to fp32 on load).
      mask: optional (p,) active-worker membership (bool or 0/1 float,
        traced).  With a mask the dynamic-order-statistic kernel runs and
        the result equals the ``masked_*`` reference on the same mask.
      op: ``median`` | ``trimmed_mean`` | ``meamed`` | ``phocas``.
      f: assumed Byzantine count (trim width / closest-count offset),
        clamped exactly as the references clamp it.
      block_n: coordinate chunk width; the grid follows the shared
        :func:`repro.kernels.gram.ref.chunk_schedule` plan at stride 1.
      interpret: run the Pallas interpreter (CPU) instead of the TPU
        lowering.
    """
    p, n = Gw.shape
    p_pad = -(-p // 8) * 8
    kept, n_pad, _ = chunk_schedule(n, block_n, 1)
    sent = jnp.asarray(_SENTINEL, Gw.dtype)
    Gp = jnp.full((p_pad, n_pad), sent, Gw.dtype).at[:p, :n].set(Gw)

    if mask is None:
        out = pl.pallas_call(
            _make_kernel(op, p, f),
            grid=(kept,),
            in_specs=[pl.BlockSpec((p_pad, block_n), lambda i: (0, i))],
            out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            interpret=interpret,
            name="coord_stats_pallas",
        )(Gp)
        return out[0, :n]

    mp = (jnp.zeros((p_pad, 1), jnp.float32)
          .at[:p, 0].set(mask.astype(jnp.float32)))
    out = pl.pallas_call(
        _make_masked_kernel(op, p, f),
        grid=(kept,),
        in_specs=[pl.BlockSpec((p_pad, block_n), lambda i: (0, i)),
                  pl.BlockSpec((p_pad, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
        name="coord_stats_pallas",
    )(Gp, mp)
    return out[0, :n]


# ---------------------------------------------------------------------------
# (W, W) distance-selection kernels (Krum / Bulyan)
# ---------------------------------------------------------------------------

def _pad_d2(D2: jnp.ndarray):
    """(p, p) -> zero-padded (p_pad8, p_pad128) fp32 tile (masked in-kernel)."""
    p = D2.shape[0]
    pr = -(-p // 8) * 8
    pc = max(128, -(-p // 128) * 128)
    return jnp.zeros((pr, pc), jnp.float32).at[:p, :p].set(
        D2.astype(jnp.float32))


def _make_krum_kernel(p: int, f: int):
    k = max(p - f - 2, 1)

    def kernel(d_ref, out_ref):
        x = d_ref[...]                                   # (pr, pc) fp32
        rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        # Self-distances and padding sort to the top, never into the k sum
        # (k <= p - 3 < p - 1 real entries per column).  Finite sentinel,
        # not inf: the sorting network's max/min compares stay NaN-free
        # and KSENTINEL holds.
        x = jnp.where((rows == cols) | (rows >= p) | (cols >= p),
                      _SENTINEL, x)
        s = _sort_rows(x)
        out_ref[...] = jnp.sum(jnp.where(rows < k, s, 0.0),
                               axis=0)[None, :]
    return kernel


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def krum_scores_pallas(D2: jnp.ndarray, *, f: int = 1,
                       interpret: bool = False) -> jnp.ndarray:
    """Krum score per worker from (p, p) squared distances -> (p,) fp32.

    Each worker's score is the sum of its p - f - 2 smallest distances to
    the *other* workers, computed with the same sorting network as the
    coordinate kernels (distances sorted ascending per column — D2 is
    symmetric — then a prefix sum of the first k rows).
    """
    p = D2.shape[0]
    out = pl.pallas_call(
        _make_krum_kernel(p, f),
        grid=(1,),
        in_specs=[pl.BlockSpec(_pad_d2(D2).shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, _pad_d2(D2).shape[1]), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, _pad_d2(D2).shape[1]),
                                       jnp.float32),
        interpret=interpret,
        name="krum_scores_pallas",
    )(_pad_d2(D2))
    return out[0, :p]


def _make_bulyan_kernel(p: int, f: int):
    theta = max(p - 2 * f, 1)
    k = max(p - f - 2, 1)

    def kernel(d_ref, out_ref):
        x0 = d_ref[...]                                  # (pr, pc) fp32
        pr, pc = x0.shape
        rows = jax.lax.broadcasted_iota(jnp.int32, (pr, pc), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (pr, pc), 1)
        valid = (rows < p) & (cols < p) & (rows != cols)
        # Same sentinel contract as aggregators.bulyan_select: masked-out
        # pairs contribute a finite `big` every round (same count per
        # column), so ordering is decided by the real part.
        big = 4.0 * jnp.max(jnp.where(valid, x0, 0.0)) + 1.0
        row_id = jax.lax.broadcasted_iota(jnp.int32, (pr, 1), 0)
        col_id = jax.lax.broadcasted_iota(jnp.int32, (1, pc), 1)

        def body(r, carry):
            avail_r, avail_c, order = carry
            pair = avail_r & avail_c                     # (pr, pc)
            # Finite sentinel (not inf) in both spots: invalid entries
            # never reach the first-k sum of a real column (p - 1 finite
            # entries >= k there), and unavailable columns only need to
            # lose every argmin against finite real scores.
            x = jnp.where(valid, jnp.where(pair, x0, big), _SENTINEL)
            s = _sort_rows(x)
            sc = jnp.sum(jnp.where(rows < k, s, 0.0), axis=0)[None, :]
            sc = jnp.where(avail_c, sc, _SENTINEL)
            pick = jnp.argmin(sc[0]).astype(jnp.int32)
            order = jnp.where(col_id == pick, r, order)
            return (avail_r & (row_id != pick),
                    avail_c & (col_id != pick), order)

        carry0 = (row_id < p, col_id < p,
                  jnp.full((1, pc), theta, jnp.int32))
        _, _, order = jax.lax.fori_loop(0, theta, body, carry0)
        out_ref[...] = order
    return kernel


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def bulyan_select_pallas(D2: jnp.ndarray, *, f: int = 1,
                         interpret: bool = False) -> jnp.ndarray:
    """Bulyan's recursive Multi-Krum selection, fused into ONE kernel.

    All theta = max(p - 2f, 1) selection rounds run inside a single
    ``pallas_call`` (a ``fori_loop`` carrying the availability mask in
    VMEM), instead of theta separate score/sort dispatches.  The kernel
    emits the *selection order* per worker (round index, or theta for
    unselected — no dynamic stores needed); the wrapper converts it to the
    (theta,) pick list of :func:`repro.core.aggregators.bulyan_select`.
    """
    p = D2.shape[0]
    theta = max(p - 2 * f, 1)
    Dp = _pad_d2(D2)
    order = pl.pallas_call(
        _make_bulyan_kernel(p, f),
        grid=(1,),
        in_specs=[pl.BlockSpec(Dp.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, Dp.shape[1]), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, Dp.shape[1]), jnp.int32),
        interpret=interpret,
        name="bulyan_select_pallas",
    )(Dp)
    # ascending selection-round order; unselected carry the theta sentinel
    return jnp.argsort(order[0, :p], stable=True)[:theta]
