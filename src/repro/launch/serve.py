"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs prefill on a prompt batch then a jitted decode loop with the
arch-appropriate cache (KV / SSM state / hybrid). Reduced configs run real
tokens on CPU; full configs are exercised via the dry-run (launch.dryrun).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg).replace(dtype="float32")
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only — nothing to decode")
    from repro.models import build_model
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    prompts = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32))
    max_seq = args.prompt_len + args.gen_len

    # prefill = teacher-forced decode over the prompt (state-carrying for
    # ssm/hybrid; cache-filling for attention)
    cache = model.init_decode_cache(args.batch, max_seq)
    decode = jax.jit(model.decode_step)
    t0 = time.time()
    for t in range(args.prompt_len):
        logits, cache = decode(params, cache, prompts[:, t], jnp.int32(t))
    prefill_s = time.time() - t0

    toks = jnp.argmax(logits, axis=-1)
    out = [toks]
    t0 = time.time()
    for t in range(args.prompt_len, max_seq - 1):
        logits, cache = decode(params, cache, toks, jnp.int32(t))
        toks = jnp.argmax(logits, axis=-1)
        out.append(toks)
    jax.block_until_ready(toks)
    decode_s = time.time() - t0
    gen = jnp.stack(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill={prefill_s*1e3:.0f}ms "
          f"decode={decode_s/max(len(out)-1,1)*1e3:.1f} ms/token")
    print(f"generated shape: {gen.shape}; sample: {np.asarray(gen[0, :12])}")
    assert np.isfinite(np.asarray(logits)).all()


if __name__ == "__main__":
    main()
