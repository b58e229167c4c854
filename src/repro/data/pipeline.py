"""Location-aware, prefetching input pipeline — the paper's machinery feeding
the training loop.

Two cooperating pieces:

* :class:`SyntheticCorpus` — deterministic token shards (seeded, reproducible
  across restarts: shard i is always the same bytes, so elastic restarts
  resume mid-epoch without data loss). Stands in for a tokenized web corpus.

* :class:`PrefetchingLoader` — the paper's proactive pipelining at step grain:
  a background thread *pre-materializes* batch k+1..k+depth and device_puts
  them (location = the consuming host/device) while step k computes, so the
  train loop's I/O hides behind the step: the paper's claim, realized with
  JAX async dispatch instead of Hercules.

The workflow integration (`epoch_workflow`) expresses a training epoch as a
TaskGraph — load tasks hinted with ``@size``/``@io_ratio``, step tasks with
``@compute-complexity`` — so the core scheduler/simulator can reason about a
REAL workload shape.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.dag import TaskGraph
from repro.core.hints import Complexity, size_hint, task

Pytree = Any


class SyntheticCorpus:
    """Deterministic sharded token stream."""

    def __init__(self, vocab: int, shard_tokens: int = 1 << 16,
                 seed: int = 0) -> None:
        self.vocab = vocab
        self.shard_tokens = shard_tokens
        self.seed = seed

    def shard(self, i: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, i))
        # zipf-ish marginal so the loss curve is non-trivial
        z = rng.zipf(1.3, self.shard_tokens).astype(np.int64)
        return (z % self.vocab).astype(np.int32)

    def batches(self, batch: int, seq: int, start_step: int = 0
                ) -> Iterator[dict[str, np.ndarray]]:
        need = batch * (seq + 1)
        per_shard = self.shard_tokens // need
        step = start_step
        while True:
            sid, off = divmod(step, max(per_shard, 1))
            data = self.shard(sid)[off * need:(off + 1) * need]
            if len(data) < need:
                step += 1
                continue
            x = data.reshape(batch, seq + 1)
            yield {"tokens": x[:, :-1], "labels": x[:, 1:]}
            step += 1


class PrefetchingLoader:
    """Double-buffered (depth-N) async loader with device placement."""

    def __init__(self, it: Iterator[dict[str, np.ndarray]], *,
                 depth: int = 2,
                 place: Callable[[Pytree], Pytree] | None = None) -> None:
        self.it = it
        self.place = place or (lambda b: jax.tree.map(jax.numpy.asarray, b))
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.waits = 0            # times the consumer found the queue empty
        self.loads = 0
        self._thread = threading.Thread(target=self._work, daemon=True,
                                        name="xflow-data-prefetch")
        self._thread.start()

    def _work(self) -> None:
        try:
            for batch in self.it:
                if self._stop.is_set():
                    return
                self.q.put(self.place(batch))   # async device transfer
                self.loads += 1
        finally:
            self.q.put(None)

    def __iter__(self):
        return self

    def __next__(self):
        if self.q.empty():
            self.waits += 1
        item = self.q.get()
        if item is None:
            raise StopIteration
        return item

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass


def epoch_workflow(cfg: ModelConfig, *, n_steps: int, n_dp: int,
                   batch: int, seq: int, step_flops: float) -> TaskGraph:
    """A training epoch as a hinted TaskGraph (consumed by core/scheduler)."""
    g = TaskGraph()
    batch_bytes = batch // n_dp * (seq + 1) * 4
    g.add_data("corpus", size_bytes=size_hint(n_steps * n_dp * batch_bytes))
    g.add_data("params0", size_bytes=size_hint(2e9))
    prev = "params0"
    for s in range(n_steps):
        parts = []
        for d in range(n_dp):
            b = f"batch_{s}_{d}"
            g.add_task(f"load_{s}_{d}", inputs=("corpus",), outputs=(b,),
                       hints=task(compute="const",
                                  io_ratio=1.0 / (n_steps * n_dp)))
            parts.append(b)
        out = f"params{s + 1}"
        g.add_task(f"step_{s}", inputs=(prev, *parts), outputs=(out,),
                   hints=task(procs=n_dp, io_ratio=1.0,
                              compute=Complexity("const",
                                                 flops_per_byte=step_flops)))
        prev = out
    return g
