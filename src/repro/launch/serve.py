"""Batched decode server (example driver).

A bounded request queue feeds a batching loop: requests are grouped into
fixed slots (continuous-batching-lite), prompts are prefilled token-by-token
into per-slot caches, then decode steps run the whole batch in lockstep —
the streaming paper's jumbo-tuple batching applied to serving.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --requests 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import make_decode_step
from repro.models import model_api


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int
    out: Optional[np.ndarray] = None
    latency_s: float = 0.0


def serve_batch(cfg, params, requests: List[Request], max_len: int = 256,
                greedy: bool = True, seed: int = 0):
    """Run one batch of requests to completion; returns the requests."""
    api = model_api(cfg)
    b = len(requests)
    step_fn = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
    cache = api.init_cache(cfg, b, max_len=max_len)
    maxp = max(len(r.prompt) for r in requests)
    pad = np.zeros((b, maxp), np.int32)
    for i, r in enumerate(requests):
        pad[i, :len(r.prompt)] = r.prompt
    t0 = time.time()
    tok = jnp.asarray(pad[:, 0])
    outs = [[] for _ in range(b)]
    # prefill (token-by-token; each step also warms the caches)
    for t in range(maxp):
        nxt, _, cache = step_fn(params, cache, jnp.asarray(pad[:, t]),
                                jnp.int32(t))
    cur = np.asarray(nxt)
    max_new = max(r.max_new for r in requests)
    for t in range(maxp, maxp + max_new):
        for i in range(b):
            outs[i].append(int(cur[i]))
        nxt, logits, cache = step_fn(params, cache, jnp.asarray(cur),
                                     jnp.int32(t))
        cur = np.asarray(nxt)
    dt = time.time() - t0
    for i, r in enumerate(requests):
        r.out = np.asarray(outs[i][:r.max_new], np.int32)
        r.latency_s = dt
    return requests, dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()
    cfg = get(args.arch, smoke=True)
    api = model_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, args.prompt_len,
                                    dtype=np.int32), args.max_new)
            for i in range(args.requests)]
    reqs, dt = serve_batch(cfg, params, reqs,
                           max_len=args.prompt_len + args.max_new + 1)
    toks = sum(r.max_new for r in reqs)
    print(f"[serve] {len(reqs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s batched)")
    for r in reqs[:2]:
        print(f"  req {r.rid}: {r.out[:10]}...")


if __name__ == "__main__":
    main()
