"""Serving launcher: batched prefill + decode with optional ADE pruning.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
        --batch 4 --prompt-len 24 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_config
from repro.models import build_model


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prune-k", type=int, default=None,
                    help="override ADE top-K KV pruning")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.prune_k is not None:
        cfg = dataclasses.replace(cfg, attn_prune_k=args.prune_k)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)

    b, t = args.batch, args.prompt_len
    max_len = t + args.gen
    prompts = jax.random.randint(jax.random.fold_in(key, 1), (b, t), 0, cfg.vocab_size)
    ctx = None
    if cfg.num_img_tokens:
        ctx = jax.random.normal(key, (b, cfg.num_img_tokens, cfg.d_model))
    if cfg.num_audio_frames:
        ctx = jax.random.normal(key, (b, cfg.num_audio_frames, cfg.d_model))

    t0 = time.perf_counter()
    logits, cache = model.prefill(params, prompts, max_len=max_len, context=ctx)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    step = jax.jit(model.decode_step, static_argnames=())
    tok = logits.argmax(-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for pos in range(t, max_len):
        logits, cache = step(params, tok, pos, cache)
        tok = logits.argmax(-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"[serve] arch={cfg.name} prune_k={cfg.attn_prune_k}")
    print(f"[serve] prefill {t}tok x{b}: {t_prefill*1e3:.1f} ms")
    print(f"[serve] decode {args.gen} steps: {t_dec*1e3:.1f} ms "
          f"({t_dec/args.gen*1e3:.1f} ms/tok incl. first-call compile)")
    print(f"[serve] sample tokens: {gen[0][:10].tolist()}")


if __name__ == "__main__":
    main()
