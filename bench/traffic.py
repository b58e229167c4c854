"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them and a seed into a schedule.

Every seed gets the same work in another order. The gaps between arrivals,
the sizes, turn counts and think times are drawn once from the mix's own
``base_seed``; the run's seed permutes the gaps (so each seed sees its own
order of quiet and busy stretches, with the same last arrival), which
session gets which sizes and think times, and draws the token ids. Runs with
different seeds then differ by the order of the work, not by its amount,
which keeps the spread between seeds near the spread of one seed.

Serving mixes (``"driver": "serve"``) describe sessions:

* ``rate_per_s``: Poisson session arrivals: ``rate_per_s * seconds``
  uniform points in the window;
* ``prompt_lens`` / ``prompt_weights``: first-turn prompt lengths, drawn from
  a fixed set so that set-up compiles every prefill shape;
* ``turn_weights``: the weights of 1, 2, ... turns per session;
* ``output_median``, ``output_sigma``: tokens per turn, lognormal, as the
  mix's source gives them; ``output_scale`` multiplies them (a cut, listed
  under the mix's ``cuts``) and ``output_clip`` bounds the result;
* ``think_mean_s``: exponential think time between a turn's last token and
  the next turn.

A mix names the source of each distribution under ``sources``, each way in
which it departs from that source, with the reason, under ``cuts``, and what
no source fixes under ``assumed``.

Workflow mixes (``"driver": "workflow"``) run workflows back to back and
only name the number of distinct input sets made in set-up.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for any whole-number seed (negative or above 2**63
    included), with optional salts for independent streams."""
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


@dataclasses.dataclass(frozen=True)
class SessionPlan:
    """One session: when its first turn is due (seconds from the window's
    start), its prompt, and per turn the tokens to serve and the think time
    before it (0 for the first)."""

    arrival_s: float
    prompt: tuple[int, ...]
    out_lens: tuple[int, ...]
    think_s: tuple[float, ...]


def serving_schedule(mix: dict, seed: int, seconds: float,
                     vocab: int) -> list[SessionPlan]:
    """Sessions due in ``[0, seconds)``, in arrival order."""
    base = np.random.default_rng(int(mix["base_seed"]))
    run = rng_for(seed, 1)
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    gaps = np.diff(np.sort(base.uniform(0.0, seconds, n)), prepend=0.0)
    arrivals = np.cumsum(run.permutation(gaps))

    lens = np.asarray(mix["prompt_lens"], int)
    weights = np.asarray(mix.get("prompt_weights", [1] * len(lens)), float)
    prompt_lens = run.permutation(
        base.choice(lens, size=n, p=weights / weights.sum()))
    tw = np.asarray(mix["turn_weights"], float)
    n_turns = run.permutation(
        base.choice(np.arange(1, len(tw) + 1), size=n, p=tw / tw.sum()))
    total = int(n_turns.sum())
    c_lo, c_hi = mix["output_clip"]
    outs = base.lognormal(np.log(mix["output_median"]), mix["output_sigma"],
                          total) * float(mix.get("output_scale", 1.0))
    outs = run.permutation(np.clip(np.round(outs), c_lo, c_hi).astype(int))
    thinks = run.permutation(base.exponential(
        float(mix.get("think_mean_s", 0.0)), total - n))

    plans, k, j = [], 0, 0
    for i in range(n):
        t = int(n_turns[i])
        think = [0.0] + [float(x) for x in thinks[j:j + t - 1]]
        j += t - 1
        plans.append(SessionPlan(
            arrival_s=float(arrivals[i]),
            prompt=tuple(int(x) for x in run.integers(0, vocab,
                                                      int(prompt_lens[i]))),
            out_lens=tuple(int(x) for x in outs[k:k + t]),
            think_s=tuple(think)))
        k += t
    return plans
