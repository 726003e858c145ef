"""Batched serving driver: online feature retrieval -> prefill -> decode.

The request path exercises the paper's low-latency plane end to end:
  1. each request names a document/session (entity id);
  2. the ONLINE store serves the session's latest context feature (its most
     recent token chunk — the "session state" pattern) via the Pallas
     lookup kernel;
  3. the model prefills the retrieved context and decodes new tokens.

Offline/online skew shows up here as a wrong prompt — the integration test
asserts the served context equals the offline latest record.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.data.loader import HOUR, TokenFeatureSet
from repro.data.sources import TokenEventSource
from repro.core.featurestore import FeatureStore
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api


def build_serving_plane(cfg, *, seed: int = 0):
    src = TokenEventSource(
        "token_stream", seed=seed, vocab_size=cfg.vocab_size,
        num_docs=64, chunk_len=32, chunks_per_bucket=128,
    )
    fs = FeatureStore("lm-serving-plane")
    fs.register_source(src)
    spec = fs.create_feature_set(TokenFeatureSet(src))
    fs.tick(now=3 * HOUR)
    return fs, spec, src


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="gemma3-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=True)
    fs, spec, src = build_serving_plane(cfg, seed=args.seed)

    # -- request batch: sessions ask for continuations -----------------------
    rng = np.random.default_rng(args.seed)
    doc_ids = rng.integers(0, src.num_docs, args.requests).astype(np.int64)

    t0 = time.perf_counter()
    ctx_vals, found = fs.get_online_features(
        spec.name, spec.version, [doc_ids]
    )
    lookup_ms = (time.perf_counter() - t0) * 1e3
    prompts = np.clip(ctx_vals.astype(np.int64), 0, cfg.vocab_size - 1)
    prompts = np.where(found[:, None], prompts, 1)  # cold sessions: BOS-ish

    params = api.init_params(jax.random.PRNGKey(args.seed), cfg,
                             max_decode_len=prompts.shape[1] + args.new_tokens)
    max_len = prompts.shape[1] + args.new_tokens
    cache = api.init_cache(cfg, args.requests, max_len)
    if cfg.encoder_decoder:
        from repro.models import encdec

        frames = np.zeros((args.requests, cfg.encoder_seq, cfg.d_model), np.float32)
        memory = encdec.encode(params, jnp.asarray(frames), cfg)
        cache = encdec.precompute_cross(params, memory, cfg, cache)

    step = jax.jit(lambda p, c, t: api.decode_step(p, c, t, cfg))

    # prefill by stepping the prompt (reference path), then decode new tokens
    toks = jnp.asarray(prompts, jnp.int32)
    t1 = time.perf_counter()
    for i in range(prompts.shape[1]):
        logits, cache = step(params, cache, toks[:, i : i + 1])
    generated = []
    cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    for _ in range(args.new_tokens):
        generated.append(np.asarray(cur)[:, 0])
        logits, cache = step(params, cache, cur)
        cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    decode_ms = (time.perf_counter() - t1) * 1e3

    out = {
        "requests": args.requests,
        "context_hits": int(found.sum()),
        "online_lookup_ms": lookup_ms,
        "decode_ms_total": decode_ms,
        "tokens_generated": int(args.new_tokens * args.requests),
        "generated": np.stack(generated, axis=1),
    }
    print(
        f"[serve] {args.requests} reqs, {out['context_hits']} warm sessions, "
        f"lookup {lookup_ms:.2f}ms, {out['tokens_generated']} tokens in "
        f"{decode_ms:.0f}ms"
    )
    return out


if __name__ == "__main__":
    enable_compile_cache()
    main()
