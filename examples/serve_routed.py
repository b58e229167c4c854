"""Location-aware multi-engine serving (compute-on-data-path for inference).

Two engines ("nodes") serve sessions; a multi-turn conversation's follow-up
requests are routed BY THE LOCATION SERVICE to the engine already holding the
session's KV cache — vs. the baseline that picks engines at random and pays a
re-prefill on every miss.

    PYTHONPATH=src python examples/serve_routed.py
"""

import dataclasses

import jax
import numpy as np

from repro.configs import get_smoke
from repro.core.locstore import LocStore
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serve.engine import Router, ServingEngine


def main() -> None:
    use_compile_cache()
    cfg = dataclasses.replace(get_smoke("granite-3-2b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    store = LocStore(2)
    engines = [ServingEngine(cfg, params, max_batch=4, max_seq=96, node=i,
                             store=store) for i in range(2)]
    router = Router(engines, store)
    rng = np.random.default_rng(0)

    # open 4 conversations
    sessions = []
    for i in range(4):
        eng = router.engine_for()
        sid = eng.submit(rng.integers(0, cfg.vocab, 8).tolist())
        sessions.append(sid)
        print(f"session {sid} opened on engine {eng.node} "
              f"(cache pinned via location service)")

    # 3 follow-up turns per session: the router finds the cache every time
    for turn in range(3):
        for sid in sessions:
            eng = router.engine_for(sid)
            eng.step()
            tokens = eng.sessions[sid].tokens
            print(f"  turn {turn}: session {sid} -> engine {eng.node} "
                  f"(hit) last_token={tokens[-1]}")

    print(f"\nlocation-service routing: {router.locality_hits} hits, "
          f"{router.locality_misses} misses")
    print(f"prefills run: {sum(e.prefills for e in engines)} "
          f"(= 4 initial; every follow-up was served from the resident cache)")


if __name__ == "__main__":
    main()
