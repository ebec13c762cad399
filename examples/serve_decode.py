"""Batched serving demo: prefill + KV-cache decode on a gemma3-family
model (sliding-window + global layers), greedy generation.

    PYTHONPATH=src python examples/serve_decode.py --batch 4 --gen 32
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.base import get_arch
from repro.models import build, count_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch("gemma3-1b").smoke()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name} (reduced): {count_params(params):,} params, "
          f"window={cfg.sliding_window}, local:global={cfg.local_global_ratio}:1")

    B, P, G = args.batch, args.prompt_len, args.gen
    max_len = P + G
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size)

    decode = jax.jit(bundle.decode_step)
    cache = bundle.init_cache(B, max_len)

    # prefill by replaying the prompt through the decode path (exactly the
    # cache the prefill kernel would produce)
    t0 = time.perf_counter()
    for t in range(P):
        logits, cache = decode(params, cache, prompts[:, t : t + 1], jnp.int32(t))
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(P, P + G - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_gen = time.perf_counter() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"prefill: {P} tokens x {B} seqs in {t_prefill:.2f}s")
    print(f"decode:  {G - 1} steps in {t_gen:.2f}s "
          f"({B * (G - 1) / max(t_gen, 1e-9):.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"  seq{b}: ...{list(map(int, prompts[b, -5:]))} -> "
              f"{list(map(int, gen[b, :8]))}...")


if __name__ == "__main__":
    main()
