"""Batched serving driver: prefill a prompt batch, decode greedily.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b \
        --reduced --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.dist.fl_step import make_serve_step
    from repro.models import init_params, prefill

    cfg = get_config(args.arch, reduced=args.reduced)
    assert cfg.causal, "serving requires a causal LM"
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    max_len = args.prompt_len + args.gen

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab)
    t0 = time.time()
    logits, caches = jax.jit(
        lambda p, x: prefill(cfg, p, x, max_len=max_len))(params, prompts)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    serve = jax.jit(make_serve_step(cfg))
    for i in range(args.gen - 1):
        tok, logits, caches = serve(params, caches,
                                    tok, jnp.int32(args.prompt_len + i))
        out.append(tok)
    gen = jnp.stack(out, 1)
    dt = time.time() - t0
    print(f"generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)", flush=True)
    print(np.asarray(gen)[: min(args.batch, 2)], flush=True)
    return np.asarray(gen)


if __name__ == "__main__":
    main()
