"""Serving launcher: batched greedy decoding with the production cache
layout (ring buffer for SWA archs, full-length otherwise).

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-9b \
        --debug --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_for_smoke
from repro.dist.serve_step import build_serve_step
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--debug", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.debug:
        cfg = reduce_for_smoke(cfg).replace(frontend=None,
                                            num_prefix_embeds=0)
    max_len = args.prompt_len + args.gen + 1
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    caches = transformer.init_caches(cfg, args.batch, max_len, jnp.float32)
    step_fn = jax.jit(build_serve_step(cfg, max_len=max_len))

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    tok = prompts[:, :1]
    for t in range(args.prompt_len):
        tok, caches = step_fn(params, caches, prompts[:, t:t + 1],
                              jnp.asarray(t, jnp.int32))
    prefill_s = time.time() - t0
    out = []
    t0 = time.time()
    for t in range(args.prompt_len, args.prompt_len + args.gen):
        out.append(tok)
        tok, caches = step_fn(params, caches, tok, jnp.asarray(t, jnp.int32))
    decode_s = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill {args.prompt_len} steps in {prefill_s:.2f}s, "
          f"decode {args.gen} steps in {decode_s:.2f}s "
          f"({args.gen * args.batch / max(decode_s, 1e-9):.1f} tok/s)")
    for row in jax.device_get(gen)[:2]:
        print("  ", row.tolist()[:16], "...")


if __name__ == "__main__":
    main()
