"""Plain float32 reference of a llama-style decoder (SmolLM family).

Pre-norm RMSNorm blocks, grouped-query causal attention with rotary
positions (rotate-half form, as the published model), a SwiGLU MLP, a
final RMSNorm and a tied or untied output head; next-token cross entropy.
Every matmul runs at ``Precision.HIGHEST``.  ``quant="fp8"`` rounds every
matmul operand to float8_e4m3fn with a per-tensor scale (the control: the
same model one precision step below bfloat16).

Weights are made here from the seed.  ``to_program`` lays them out as the
program's parameter pytree; the program rotates interleaved pairs, so the
q and k columns of each head are permuted into that order, which makes
the two forward passes the same function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    """Round to float8_e4m3fn with a per-tensor scale; gradient passes
    straight through."""
    scale = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _operand(x, quant):
    return _fp8(x) if quant == "fp8" else x


def mm(a, b, quant=None):
    return jnp.matmul(_operand(a, quant), _operand(b, quant),
                      precision=HIGHEST)


def einsum(spec, a, b, quant=None):
    return jnp.einsum(spec, _operand(a, quant), _operand(b, quant),
                      precision=HIGHEST)


def dims(c: dict):
    d, H, KV = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    return d, H, KV, d // H


def init(key, c: dict) -> dict:
    """Weights in this module's layout, every layer stacked on axis 0."""
    d, H, KV, hd = dims(c)
    L, ff, V = c["num_hidden_layers"], c["intermediate_size"], c["vocab_size"]
    mats = {"wq": (L, d, H * hd), "wk": (L, d, KV * hd),
            "wv": (L, d, KV * hd), "wo": (L, H * hd, d),
            "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    keys = jax.random.split(key, len(mats) + 2)
    p = {"embed": jax.random.normal(keys[0], (V, d), jnp.float32) * d ** -0.5,
         "final_norm": jnp.ones((d,), jnp.float32),
         "attn_norm": jnp.ones((L, d), jnp.float32),
         "mlp_norm": jnp.ones((L, d), jnp.float32)}
    for k, (name, shape) in zip(keys[1:], mats.items()):
        p[name] = (jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                               jnp.float32)
                   * shape[-2] ** -0.5)
    if not c["tie_word_embeddings"]:
        p["lm_head"] = (jax.random.normal(keys[-1], (V, d), jnp.float32)
                        * d ** -0.5)
    return p


def _interleave(n_heads: int, hd: int) -> np.ndarray:
    """Column order that takes rotate-half heads to interleaved pairs."""
    half = hd // 2
    idx = np.empty(n_heads * hd, np.int32)
    for h in range(n_heads):
        idx[h * hd + 0:h * hd + hd:2] = h * hd + np.arange(half)
        idx[h * hd + 1:h * hd + hd:2] = h * hd + half + np.arange(half)
    return idx


def to_program(p: dict, c: dict, period: int = 1) -> dict:
    """The same weights as the program's parameter pytree.

    The program scans its layers in periods of ``period`` blocks (its
    ``ModelConfig.period``; 1 for the published configurations): block j
    of the period holds layers j, j + period, ... stacked.
    """
    _, H, KV, hd = dims(c)

    def block(j):
        def take(x):
            return x[j::period]
        return {
            "norm1": {"scale": take(p["attn_norm"])},
            "mixer": {"wq": {"w": take(p["wq"])[..., _interleave(H, hd)]},
                      "wk": {"w": take(p["wk"])[..., _interleave(KV, hd)]},
                      "wv": {"w": take(p["wv"])}, "wo": {"w": take(p["wo"])}},
            "norm2": {"scale": take(p["mlp_norm"])},
            "ffn": {"up": {"w": take(p["w_up"])},
                    "gate": {"w": take(p["w_gate"])},
                    "down": {"w": take(p["w_down"])}},
        }

    out = {"embed": {"table": p["embed"]},
           "final_norm": {"scale": p["final_norm"]},
           "head": [], "tail": [],
           "body": [block(j) for j in range(period)]}
    if "lm_head" in p:
        out["unembed"] = {"table": p["lm_head"]}
    return out


def from_program(t: dict, c: dict, period: int = 1) -> dict:
    """The inverse of ``to_program``: a pytree in the program's layout
    (weights, a gradient, Adam's moments) in this module's layout."""
    _, H, KV, hd = dims(c)
    body = t["body"]

    def stack(get, cols=None):
        blocks = [get(b) for b in body]
        x = jnp.stack(blocks, 1).reshape((-1,) + blocks[0].shape[1:])
        return x if cols is None else x[..., np.argsort(cols)]

    p = {"embed": t["embed"]["table"],
         "final_norm": t["final_norm"]["scale"],
         "attn_norm": stack(lambda b: b["norm1"]["scale"]),
         "wq": stack(lambda b: b["mixer"]["wq"]["w"], _interleave(H, hd)),
         "wk": stack(lambda b: b["mixer"]["wk"]["w"], _interleave(KV, hd)),
         "wv": stack(lambda b: b["mixer"]["wv"]["w"]),
         "wo": stack(lambda b: b["mixer"]["wo"]["w"]),
         "mlp_norm": stack(lambda b: b["norm2"]["scale"]),
         "w_up": stack(lambda b: b["ffn"]["up"]["w"]),
         "w_gate": stack(lambda b: b["ffn"]["gate"]["w"]),
         "w_down": stack(lambda b: b["ffn"]["down"]["w"])}
    if "unembed" in t:
        p["lm_head"] = t["unembed"]["table"]
    return p


# ---------------------------------------------------------------------------
# what the program has to run, and what the architecture costs
# ---------------------------------------------------------------------------

def expect(c: dict) -> dict:
    """The program's ``ModelConfig`` fields (``layer_kinds`` as a set)
    that running this architecture as the file states fixes."""
    return {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "num_layers": c["num_hidden_layers"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"],
            "use_bias": c["attention_bias"], "act": c["hidden_act"],
            "param_dtype": c["param_dtype"],
            "compute_dtype": c["compute_dtype"],
            "norm": "rmsnorm", "gated_mlp": True, "pos": "rope",
            "rope_fraction": 1.0, "window": None, "moe": None,
            "logit_softcap": 0.0, "layer_kinds": {"attn"}}


def layer_params(c: dict) -> int:
    """Parameters of one decoder layer (no biases)."""
    d, H, KV, hd = dims(c)
    q, kv = H * hd, KV * hd
    return (d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]
            + 2 * d)


def param_count(c: dict) -> int:
    """All parameters: layers, final norm, the embedding table (and the
    output head when it is not tied)."""
    emb = c["vocab_size"] * c["hidden_size"]
    head = 0 if c["tie_word_embeddings"] else emb
    return (c["num_hidden_layers"] * layer_params(c) + c["hidden_size"]
            + emb + head)


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matmul per token: every parameter but
    the embedding lookup.  A tied table is counted once, as the output
    head; an untied model's lookup table is left out."""
    n = param_count(c)
    if not c["tie_word_embeddings"]:
        n -= c["vocab_size"] * c["hidden_size"]
    return n


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Causal attention, forward and backward: 6 * S * d_attn * L, the
    causal half of QK^T and AV."""
    _, H, _, hd = dims(c)
    return 6.0 * seq * H * hd * c["num_hidden_layers"]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, heads, S, hd); rotate halves."""
    S, hd = x.shape[-2], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, c, quant):
    d, H, KV, hd = dims(c)
    B, S, _ = x.shape
    eps = c["rms_norm_eps"]
    h = _rms(x, lp["attn_norm"], eps)
    q = mm(h, lp["wq"], quant).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = mm(h, lp["wk"], quant).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    v = mm(h, lp["wv"], quant).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = einsum("bhqd,bhkd->bhqk", q, k, quant) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + mm(o.transpose(0, 2, 1, 3).reshape(B, S, H * hd), lp["wo"],
               quant)
    h = _rms(x, lp["mlp_norm"], eps)
    g = jax.nn.silu(mm(h, lp["w_gate"], quant)) * mm(h, lp["w_up"], quant)
    return x + mm(g, lp["w_down"], quant)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down")


def loss(p, tokens, labels, c: dict, quant=None, loss_mask=None):
    """Mean next-token cross entropy of one worker's (B, S) batch."""
    x = p["embed"][tokens]
    layers = {k: p[k] for k in LAYER_KEYS}

    @jax.checkpoint
    def body(x, lp):
        return _layer(x, lp, c, quant), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x, p["final_norm"], c["rms_norm_eps"])
    head = p["lm_head"] if "lm_head" in p else p["embed"]
    logits = mm(x, head.T, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if loss_mask is None:
        return jnp.mean(nll)
    mask = jnp.broadcast_to(loss_mask, nll.shape)
    return jnp.sum(nll * mask) / jnp.sum(mask)
