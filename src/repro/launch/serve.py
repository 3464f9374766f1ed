"""V-BOINC serving launcher: batched prefill + decode inside a capsule.

Serves a reduced-config model on CPU: a request queue is batched, prefilled
once, then decoded token-by-token with the KV/SSM caches — the inference
twin of the training driver (the paper's 'run typical BOINC projects'
claim: the same capsule mechanism hosts a serving workload unchanged).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --requests 8 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, reduced
from repro.distributed.sharding import init_tree
from repro.launch.jaxcache import use_compile_cache
from repro.models import api
from repro.models.lm import RunConfig


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = reduced(get_arch(args.arch))
    run = RunConfig(remat="none", block_kv=128, ssm_chunk=32)
    params = init_tree(api.param_specs(cfg), jax.random.key(args.seed))
    max_len = args.prompt_len + args.gen

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len)).astype(np.int32)
    batch = {"tokens": prompts}
    if cfg.enc_dec:
        batch["frames"] = rng.standard_normal(
            (args.requests, args.prompt_len, cfg.d_model)).astype(np.float32)

    prefill = jax.jit(api.make_prefill_step(cfg, max_len, run))
    decode = jax.jit(api.make_decode_step(cfg, run))

    t0 = time.time()
    logits, caches = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    def sample(lg, key):
        lg = lg[..., :cfg.vocab_size]
        if args.temperature <= 0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / args.temperature).astype(
            jnp.int32)

    key = jax.random.key(args.seed)
    tok = np.asarray(sample(logits, key))[:, None]
    generated = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        key, sub = jax.random.split(key)
        logits, caches = decode(params, caches,
                                {"tokens": jnp.asarray(tok),
                                 "index": jnp.int32(args.prompt_len + i)})
        tok = np.asarray(sample(logits[:, 0], sub))[:, None]
        generated.append(tok)
    t_decode = time.time() - t0

    out_tokens = np.concatenate(generated, axis=1)
    tps = args.requests * (args.gen - 1) / max(t_decode, 1e-9)
    summary = {
        "arch": cfg.name, "requests": args.requests,
        "prefill_s": round(t_prefill, 3),
        "decode_s": round(t_decode, 3),
        "decode_tokens_per_s": round(tps, 1),
        "sample_output": out_tokens[0, :8].tolist(),
    }
    print(json.dumps(summary, indent=2))
    return summary


if __name__ == "__main__":
    main()
