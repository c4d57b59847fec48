"""Transformer assembler: config -> init / forward / loss / decode.

Layer stack execution
---------------------
``cfg.block_pattern`` defines a repeating period (e.g. ``('rglru','rglru',
'attn')``).  The stack splits into:

  head   — ``cfg.moe_skip_first`` puts layer 0 (deepseek's dense-FFN layer)
           outside the scan,
  body   — all full periods, executed as ONE ``lax.scan`` over stacked
           params (HLO size O(period), independent of depth: this is what
           keeps 40 multi-pod dry-run compiles tractable),
  tail   — the non-period remainder (e.g. recurrentgemma's 38 = 12*3 + 2),
           applied unstacked.

Blocks are pre-norm residual: ``x += mixer(norm(x))``; attention blocks are
followed by a second ``x += ffn(norm(x))`` (dense MLP or MoE); recurrent
blocks (mlstm/slstm) carry their own internal FFN per the xLSTM design when
``d_ff == 0``, otherwise they too get the ffn.

Caches mirror the head/body/tail structure; the body cache is a stacked
pytree scanned alongside the params.  The decode step counter is one scalar.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models import (attention, layers, mlp as mlp_lib, moe as moe_lib,
                          rglru as rglru_lib, ssm)
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    return kind in ("attn", "rglru") and (cfg.d_ff > 0 or cfg.moe is not None)


def block_init(key, cfg: ModelConfig, kind: str, layer_idx: int):
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p: dict[str, Any] = {"norm1": layers.norm_init(cfg.d_model, cfg.norm, dt)}
    if kind == "attn":
        p["mixer"] = attention.attn_init(ks[0], cfg)
    elif kind == "mlstm":
        p["mixer"] = ssm.mlstm_block_init(ks[0], cfg)
    elif kind == "slstm":
        p["mixer"] = ssm.slstm_block_init(ks[0], cfg)
    elif kind == "rglru":
        p["mixer"] = rglru_lib.rglru_block_init(ks[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if _has_ffn(cfg, kind):
        p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm, dt)
        if cfg.is_moe_layer(layer_idx):
            p["ffn"] = moe_lib.moe_init(ks[1], cfg)
        else:
            d_ff = cfg.dense_d_ff_first if (cfg.moe_skip_first
                                            and layer_idx == 0) else cfg.d_ff
            p["ffn"] = mlp_lib.mlp_init(ks[1], cfg, d_ff=d_ff)
    return p


def block_apply(p, x, cfg: ModelConfig, kind: str, *, positions,
                is_moe: bool, cache=None, decode=False, step=None,
                ring=False, attn_impl="xla"):
    """Returns (x, new_cache, aux_losses)."""
    h = layers.apply_norm(p["norm1"], x, cfg.norm)
    new_cache = cache
    with jax.named_scope("attention"):       # the mixer, whichever kind
        if kind == "attn":
            if decode:
                out, new_cache = attention.attn_decode(
                    p["mixer"], h, cfg, cache, step=step, ring=ring)
            else:
                out = attention.attn_apply(p["mixer"], h, cfg,
                                           positions=positions,
                                           impl=attn_impl)
        elif kind == "mlstm":
            out, new_cache = ssm.mlstm_block_apply(
                p["mixer"], h, cfg, cache, chunk=1 if decode else 256)
        elif kind == "slstm":
            out, new_cache = ssm.slstm_block_apply(p["mixer"], h, cfg, cache)
        elif kind == "rglru":
            out, new_cache = rglru_lib.rglru_block_apply(p["mixer"], h, cfg,
                                                         cache)
        else:
            raise ValueError(kind)
    x = x + out.astype(x.dtype)

    losses = {}
    if "ffn" in p:
        h = layers.apply_norm(p["norm2"], x, cfg.norm)
        with jax.named_scope("mlp"):         # dense MLP or MoE
            if is_moe:
                out, losses = moe_lib.moe_apply(p["ffn"], h, cfg)
            else:
                out = mlp_lib.mlp_apply(p["ffn"], h, cfg)
        x = x + out.astype(x.dtype)
    return x, new_cache, losses


def block_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    if kind == "attn":
        return attention.init_cache(cfg, batch, max_len, dtype)
    if kind == "mlstm":
        d_in = int(cfg.mlstm_proj_factor * cfg.d_model)
        H = cfg.num_heads
        return (ssm.mlstm_state_init(batch, H, d_in // H, d_in // H),
                jnp.zeros((batch, cfg.conv_width - 1, d_in), dtype))
    if kind == "slstm":
        return ssm.slstm_state_init(batch, cfg.num_heads,
                                    cfg.d_model // cfg.num_heads)
    if kind == "rglru":
        d_rnn = cfg.rglru_width or cfg.d_model
        return (jnp.zeros((batch, d_rnn), jnp.float32),
                jnp.zeros((batch, cfg.conv_width - 1, d_rnn), dtype))
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack layout
# ---------------------------------------------------------------------------

def stack_layout(cfg: ModelConfig):
    """-> (head_kinds, n_periods, period_kinds, tail_kinds) with layer idx."""
    kinds = cfg.layer_kinds()
    off = 1 if cfg.moe_skip_first else 0
    head = tuple((i, kinds[i]) for i in range(off))
    body_layers = len(kinds) - off
    period = cfg.period
    n_periods = body_layers // period
    body_start = off
    tail_start = off + n_periods * period
    period_kinds = tuple(kinds[body_start:body_start + period])
    tail = tuple((i, kinds[i]) for i in range(tail_start, len(kinds)))
    return head, n_periods, period_kinds, body_start, tail


def init_params(key, cfg: ModelConfig):
    head, n_periods, period_kinds, body_start, tail = stack_layout(cfg)
    k_embed, k_head, k_body, k_tail, k_fe, k_out = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)

    params: dict[str, Any] = {
        "embed": layers.embedding_init(k_embed, cfg.vocab_size, cfg.d_model, dt),
        "final_norm": layers.norm_init(cfg.d_model, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = layers.embedding_init(k_out, cfg.vocab_size,
                                                  cfg.d_model, dt)
    if cfg.frontend is not None:
        ks = jax.random.split(k_fe, 2)
        params["frontend"] = {
            "proj1": layers.linear_init(ks[0], cfg.d_frontend, cfg.d_model,
                                        dtype=dt, axes=(None, "embed")),
            "proj2": layers.linear_init(ks[1], cfg.d_model, cfg.d_model,
                                        dtype=dt, axes=("embed", "embed")),
        }

    params["head"] = [block_init(jax.random.fold_in(k_head, i), cfg, kind, i)
                      for i, kind in head]

    if n_periods > 0:
        def one_period(k):
            kk = jax.random.split(k, len(period_kinds))
            # layer_idx within body: any body layer works for is_moe/shape
            return [block_init(kk[j], cfg, kind, body_start + j)
                    for j, kind in enumerate(period_kinds)]
        period_keys = jax.random.split(k_body, n_periods)
        # python loop + tree-stack (not vmap: sharding constraints inside
        # init lack batching rules); init HLO stays O(n_periods), forward
        # HLO stays O(1) via the scan.
        periods = [one_period(k) for k in period_keys]
        params["body"] = jax.tree.map(lambda *xs: jnp.stack(xs), *periods)
    else:
        params["body"] = None

    params["tail"] = [block_init(jax.random.fold_in(k_tail, i), cfg, kind, i)
                      for i, kind in tail]
    return params


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    head, n_periods, period_kinds, body_start, tail = stack_layout(cfg)
    caches: dict[str, Any] = {
        "head": [block_cache_init(cfg, kind, batch, max_len, dtype)
                 for _, kind in head],
        "tail": [block_cache_init(cfg, kind, batch, max_len, dtype)
                 for _, kind in tail],
    }
    if n_periods > 0:
        one = [block_cache_init(cfg, kind, batch, max_len, dtype)
               for kind in period_kinds]
        caches["body"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), one)
    else:
        caches["body"] = None
    return caches


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+ frontend prefix) embedding.  Returns (x, positions, loss_mask)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    B, S_tok = tokens.shape
    x = layers.embed(params["embed"], tokens, cdt)
    loss_mask = batch.get("loss_mask")
    if cfg.frontend is not None and "prefix_embeds" in batch:
        fe = params["frontend"]
        pe = layers.linear(fe["proj2"],
                           jax.nn.gelu(layers.linear(fe["proj1"],
                                                     batch["prefix_embeds"],
                                                     cdt)), cdt)
        x = jnp.concatenate([pe, x], axis=1)
        pm = jnp.zeros((B, pe.shape[1]), bool)
        tm = loss_mask if loss_mask is not None else jnp.ones((B, S_tok), bool)
        loss_mask = jnp.concatenate([pm, tm], axis=1)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos == "sinusoidal":
        x = x + layers.sinusoidal_positions(positions, cfg.d_model).astype(cdt)
    x = shard(x, ("sub_batch", "seq", "embed"))
    return x, positions, loss_mask


def apply_stack(params, x, cfg: ModelConfig, *, positions, caches=None,
                decode=False, step=None, ring=False, attn_impl="xla"):
    """Run head + scanned body + tail.  Returns (x, caches, aux_losses)."""
    head, n_periods, period_kinds, body_start, tail = stack_layout(cfg)
    total_losses: dict[str, jnp.ndarray] = {}
    new_caches = {"head": [], "tail": [], "body": None}

    def acc_losses(losses):
        for k_, v_ in losses.items():
            total_losses[k_] = total_losses.get(k_, 0.0) + v_

    # Training path: rematerialize each block in the backward pass so the
    # stash per layer is only the residual stream (production default —
    # without this the 4k training activations of the large archs exceed
    # HBM; quantified in EXPERIMENTS.md §Perf).
    use_remat = cfg.remat and caches is None

    def run_block(p, h, kind, is_moe):
        def fn(p_, h_):
            y, _, ls = block_apply(p_, h_, cfg, kind, positions=positions,
                                   is_moe=is_moe, cache=None, decode=False,
                                   step=step, ring=ring, attn_impl=attn_impl)
            return y, ls
        if use_remat:
            fn = jax.checkpoint(fn)
        return fn(p, h)

    for j, (i, kind) in enumerate(head):
        if caches is None:
            x, ls = run_block(params["head"][j], x, kind,
                              cfg.is_moe_layer(i))
            nc = None
        else:
            x, nc, ls = block_apply(params["head"][j], x, cfg, kind,
                                    positions=positions,
                                    is_moe=cfg.is_moe_layer(i),
                                    cache=caches["head"][j], decode=decode,
                                    step=step, ring=ring, attn_impl=attn_impl)
        new_caches["head"].append(nc)
        acc_losses(ls)

    if n_periods > 0:
        is_moe_body = cfg.moe is not None

        def body_fn(carry, xs):
            h = carry
            if caches is not None:
                p_period, c_period = xs
            else:
                p_period, c_period = xs, [None] * len(period_kinds)
            nc_list = []
            ls_acc = None
            for j, kind in enumerate(period_kinds):
                is_moe = is_moe_body and kind == "attn"
                if caches is None:
                    h, ls = run_block(p_period[j], h, kind, is_moe)
                    nc = None
                else:
                    h, nc, ls = block_apply(
                        p_period[j], h, cfg, kind, positions=positions,
                        is_moe=is_moe, cache=c_period[j], decode=decode,
                        step=step, ring=ring, attn_impl=attn_impl)
                nc_list.append(nc)
                vals = [ls.get("moe_aux", jnp.zeros((), jnp.float32)),
                        ls.get("moe_z", jnp.zeros((), jnp.float32))]
                ls_acc = vals if ls_acc is None else [a + b for a, b
                                                      in zip(ls_acc, vals)]
            return h, (nc_list if caches is not None else None,
                       jnp.stack(ls_acc))

        xs = (params["body"], caches["body"]) if caches is not None \
            else params["body"]
        if cfg.scan_layers:
            x, (body_caches, ls_stack) = jax.lax.scan(body_fn, x, xs)
            ls_sum = jnp.sum(ls_stack, axis=0)
        else:
            # unrolled (dry-run roofline mode): identical math, O(L) HLO
            ys = []
            for i in range(n_periods):
                xi = jax.tree.map(lambda t, i=i: t[i], xs)
                x, y = body_fn(x, xi)
                ys.append(y)
            body_caches = (jax.tree.map(lambda *ts: jnp.stack(ts),
                                        *[y[0] for y in ys])
                           if caches is not None else None)
            ls_sum = sum(y[1] for y in ys)
        new_caches["body"] = body_caches
        acc_losses({"moe_aux": ls_sum[0], "moe_z": ls_sum[1]})

    for j, (i, kind) in enumerate(tail):
        if caches is None:
            x, ls = run_block(params["tail"][j], x, kind,
                              cfg.is_moe_layer(i))
            nc = None
        else:
            x, nc, ls = block_apply(params["tail"][j], x, cfg, kind,
                                    positions=positions,
                                    is_moe=cfg.is_moe_layer(i),
                                    cache=caches["tail"][j], decode=decode,
                                    step=step, ring=ring,
                                    attn_impl=attn_impl)
        new_caches["tail"].append(nc)
        acc_losses(ls)

    return x, (new_caches if caches is not None else None), total_losses


def _logits(params, x, cfg: ModelConfig):
    """Final norm, then the (tied or separate) unembedding."""
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = layers.unembed(table, x, jnp.dtype(cfg.compute_dtype))
    logits = layers.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return shard(logits, ("sub_batch", "seq", "vocab"))


def forward(params, batch, cfg: ModelConfig, *, attn_impl="xla"):
    """Training/eval forward.  Returns (loss, metrics)."""
    with jax.named_scope("embed"):
        x, positions, loss_mask = _embed_inputs(params, batch, cfg)
    x, _, aux = apply_stack(params, x, cfg, positions=positions,
                            attn_impl=attn_impl)
    with jax.named_scope("lm_head"):
        logits = _logits(params, x, cfg)

        labels = batch["labels"]
        if logits.shape[1] != labels.shape[1]:      # frontend prefix present
            prefix = logits.shape[1] - labels.shape[1]
            pad_lab = jnp.zeros((labels.shape[0], prefix), labels.dtype)
            labels = jnp.concatenate([pad_lab, labels], axis=1)
        if loss_mask is None:
            loss_mask = jnp.ones(labels.shape, bool)

        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(loss_mask), 1)
        loss = jnp.sum(nll * loss_mask) / denom
    total = loss + sum(aux.values()) if aux else loss
    metrics = {"loss": loss, **aux,
               "ppl_proxy": jnp.exp(jnp.clip(loss, 0, 20.0))}
    return total, metrics


def decode_step(params, token, caches, step, cfg: ModelConfig, *,
                max_len: int):
    """One-token serve step.  token: (B, 1) -> (logits (B,1,V), caches)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B = token.shape[0]
    positions = jnp.broadcast_to(step[None, None], (B, 1))
    with jax.named_scope("embed"):
        x = layers.embed(params["embed"], token, cdt)
        if cfg.pos == "sinusoidal":
            x = x + layers.sinusoidal_positions(positions,
                                                cfg.d_model).astype(cdt)
    ring = attention.cache_is_ring(cfg, max_len)
    x, caches, _ = apply_stack(params, x, cfg, positions=positions,
                               caches=caches, decode=True, step=step,
                               ring=ring)
    with jax.named_scope("lm_head"):
        return _logits(params, x, cfg), caches


def prefill(params, batch, cfg: ModelConfig, *, attn_impl="xla"):
    """Full-sequence forward returning logits (inference prefill path)."""
    with jax.named_scope("embed"):
        x, positions, _ = _embed_inputs(params, batch, cfg)
    x, _, _ = apply_stack(params, x, cfg, positions=positions,
                          attn_impl=attn_impl)
    with jax.named_scope("lm_head"):
        return _logits(params, x, cfg)


# ---------------------------------------------------------------------------
# parameter counting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _count_cache(cfg: ModelConfig):
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    total = 0
    routed = 0
    embed = 0
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for path, leaf in flat:
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
        keys = "/".join(str(p) for p in path)
        if "'ffn'" in keys and ("w_up" in keys or "w_gate" in keys
                                or "w_down" in keys) and "shared" not in keys:
            routed += n
        if "'embed'" in keys or "'unembed'" in keys:
            embed += n
    return total, routed, embed


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    total, routed, _ = _count_cache(cfg)
    if active_only and cfg.moe is not None:
        total = total - routed + routed * cfg.moe.top_k // cfg.moe.num_experts
    return total


def count_embedding_params(cfg: ModelConfig) -> int:
    return _count_cache(cfg)[2]
