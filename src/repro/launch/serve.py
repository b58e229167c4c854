"""Serving launcher: batched generation with location-aware routing.

``python -m repro.launch.serve --arch <id> --engines 2 --requests 12``

Runs smoke-scale engines on CPU; demonstrates the cross-layer serving path:
sessions pinned in the location service, follow-up requests routed to the
engine holding the KV cache (compute-on-data-path for inference).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import ARCH_NAMES, get_smoke
from repro.core.locstore import LocStore
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serve.engine import Router, ServingEngine

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="granite-3-2b")
    ap.add_argument("--engines", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_smoke(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    store = LocStore(args.engines)
    engines = [ServingEngine(cfg, params, max_batch=args.max_batch,
                             max_seq=96, node=i, store=store)
               for i in range(args.engines)]
    router = Router(engines, store)
    rng = np.random.default_rng(0)

    t0 = time.perf_counter()
    sessions = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=8).tolist()
        eng = router.engine_for()
        sid = eng.submit(prompt)
        sessions.append((eng, sid))
        print(f"req {i}: engine {eng.node} slot session {sid}")
    # decode everything to completion, round-robin across engines
    for _ in range(args.max_new):
        for eng in engines:
            eng.step()
    for eng, sid in sessions:
        toks = eng.finish(sid)
        print(f"engine {eng.node} session {sid}: {toks[:args.max_new]}")
    dt = time.perf_counter() - t0
    total_tokens = sum(len(e.finish(s)) for e, s in sessions)
    print(f"\n{args.requests} requests, {total_tokens} tokens, "
          f"{dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    print("router locality:", router.locality_hits, "hits /",
          router.locality_misses, "misses")


if __name__ == "__main__":
    main()
