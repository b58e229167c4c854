"""Serving cells: sessions of one or more turns, offered in an open loop to
``Router`` -> ``ServingEngine`` -> ``LocStore`` on one chip.

Sessions arrive on the mix's schedule (``bench/traffic.py``). A due turn
waits in one FIFO queue and is admitted while the engine has a free slot: a
first turn is prefilled (``ServingEngine.submit``), a later one resumes the
session's parked KV cache through ``Router.follow_up``. The loop then steps
the engine, which decodes one token for every slotted session, and ends each
turn once it has served its tokens: a non-final turn parks its session
(``ServingEngine.park``) and schedules the next turn after a think time; a
final turn finishes it, as does a turn whose next one would fall after the
window. Turns due in the window run to their end.

* ``itl_mean_ms``: the mean of every gap between consecutive tokens of the
  turns due in the window: the window's time per token, stalls behind
  other sessions' prefills and resumes included.

Standard error also carries the time to first token (from a turn's due time
to its first token: the prefill's for a first turn, the first decoded after
the resume for a later one), its p50 and p90 over all turns and its mean
over the later turns, and the gaps' p99. Over the 60-80 turns of a window
these swing by more than half of any bound a check may hold them to.

Set-up makes the weights from the seed, builds the engine and warms every
shape the mix uses: a prefill per prompt length, every slot written, read
back by a park and written again by a resume, and the decode step. After the
window, the program's state is freed and a sample of finished sessions,
drawn from the seed with the longest among them, is run through the
configuration's plain reference over weights it makes from the seed itself:
the widest gap by which a served token's logit lies below the reference's
best is compared with the limit. A mix with ``control`` set (calibration and
the tests) puts the reference in the next precision down in the program's
place: the gap of the token it would choose is what is compared.
"""

from __future__ import annotations

import collections
import gc
import heapq
import math
import time
from typing import Callable

import jax
import numpy as np

from bench import flops, traffic
from bench.harness import (CellRun, Readings, Spans, TraceWindow, peak_bytes,
                           percentile)

DRAIN_CAP_S = 60.0       # turns due in the window get this long to finish


class _Turn:
    __slots__ = ("plan", "k", "due", "seen", "admitted", "sid", "times",
                 "want")

    def __init__(self, plan: int, k: int, due: float, seen: float,
                 want: int) -> None:
        self.plan, self.k, self.due, self.seen, self.want = plan, k, due, seen, want
        self.admitted: float | None = None
        self.sid: int | None = None
        self.times: list[float] = []


def build(cfg: dict, cfg_mod, seed: int, hook: Callable | None = None):
    """Weights from the seed and the serving stack the cell drives."""
    from repro.core.config import ServingConfig
    from repro.core.locstore import LocStore, tiered_hierarchy
    from repro.models import init_params
    from repro.serve.engine import Router, ServingEngine

    mcfg = cfg_mod.model_config(cfg)
    weights = cfg_mod.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    want = jax.eval_shape(lambda k: init_params(mcfg, k),
                          jax.ShapeDtypeStruct((2,), np.uint32))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), weights)
    if jax.tree.map(lambda x: (x.shape, x.dtype), want) != got:
        raise ValueError("the benchmark's weights do not match the program's "
                         "parameter layout")
    sc = ServingConfig(max_batch=int(cfg["serving"]["max_batch"]),
                       max_seq=int(cfg["serving"]["max_seq"]))
    store = LocStore(1, hierarchy=tiered_hierarchy())
    eng = ServingEngine(mcfg, weights, config=sc, node=0, store=store)
    if hook is not None:
        hook(eng)
    router = Router([eng], store, config=sc)
    return weights, eng, router


def warm(eng, router, mix: dict, vocab: int) -> None:
    """Compile every shape the window uses: each prompt length's prefill,
    every slot's write, and (for multi-turn mixes) every slot's park and
    resume, and the decode step."""
    rng = np.random.default_rng(0)
    lens = list(mix["prompt_lens"])
    sids = []
    for i in range(eng.max_batch):
        n = lens[i % len(lens)]
        sids.append(eng.submit(rng.integers(0, vocab, n).tolist()))
    for n in lens[eng.max_batch:]:           # lengths beyond the slot count
        eng.finish(sids.pop(0))
        sids.append(eng.submit(rng.integers(0, vocab, n).tolist()))
    eng.step()
    if len(mix["turn_weights"]) > 1:
        for sid in sids:                     # one parked at a time
            eng.park(sid)
            d = router.follow_up(sid, [])
            if d.kind != "hit_parked" or not d.resumed:
                raise RuntimeError(f"warm-up resume of {sid} was {d}")
        eng.step()
    for sid in sids:
        eng.finish(sid)
    jax.block_until_ready(eng.state)


def _sample(rng: np.random.Generator, served: dict[int, list[int]],
            want_tokens: int) -> list[int]:
    """Plans to check: the one that served most tokens, then others in an
    order drawn from the seed, until ``want_tokens`` tokens are covered."""
    if not served:
        return []
    order = sorted(served)
    longest = max(order, key=lambda i: (len(served[i]), -i))
    rest = [i for i in rng.permutation(order).tolist() if i != longest]
    out, n = [longest], len(served[longest])
    for i in rest:
        if n >= want_tokens:
            break
        out.append(i)
        n += len(served[i])
    return out


def run(cfg: dict, cfg_mod, mix: dict, *, cell: str, seed: int,
        seconds: float, trace: bool, devices, peaks: dict, clock,
        t_start: float, log: Callable[[str], None],
        hook: Callable | None = None) -> CellRun:
    vocab = int(cfg["vocab_size"])
    weights, eng, router = build(cfg, cfg_mod, seed, hook)
    warm(eng, router, mix, vocab)
    stats = devices[0].memory_stats() or {}
    hbm = {k: int(stats.get(k, 0))
           for k in ("bytes_limit", "bytes_in_use", "peak_bytes_in_use")}
    slot_bytes = eng.slot_bytes()
    log(f"[serve] set-up: {clock.since(0)[0]} compiles {clock.since(0)[1]:.2f}"
        f" s; HBM after set-up {hbm}; headroom over the peak "
        f"{(hbm['bytes_limit'] - hbm['peak_bytes_in_use']) / slot_bytes:.1f}"
        f" and over what is in use "
        f"{(hbm['bytes_limit'] - hbm['bytes_in_use']) / slot_bytes:.1f} "
        f"parked slices of {slot_bytes:.0f} bytes")
    plans = traffic.serving_schedule(mix, seed, seconds, vocab)
    spans = Spans(trace)
    tw = TraceWindow(cell, seconds) if trace else None
    c_mark = clock.mark()
    base = {"prefills": eng.prefills, "parks": eng.parks,
            "resumes": eng.resumes, "steps": eng.steps}
    setup_s = time.perf_counter() - t_start

    arrivals = collections.deque(range(len(plans)))
    followups: list[tuple[float, int, int]] = []
    queue: collections.deque[_Turn] = collections.deque()
    active: dict[int, _Turn] = {}
    sid_of: dict[int, int] = {}
    turns: list[_Turn] = []
    failed_plans: set[int] = set()
    served: dict[int, list[int]] = {}
    n_failed = 0
    parked_now, parked_max, parked_sum, iters = 0, 0, 0, 0
    flops_done = 0.0
    traced_bytes = traced_flops = 0.0

    def fail(tr: _Turn, err: Exception) -> None:
        nonlocal n_failed
        n_failed += 1
        failed_plans.add(tr.plan)
        log(f"[serve] turn {tr.k} of session {tr.plan} failed: {err!r}")

    def end_turn(tr: _Turn, t: float) -> None:
        nonlocal parked_now
        del active[tr.sid]
        plan = plans[tr.plan]
        nxt = tr.k + 1
        if nxt < len(plan.out_lens) and t + plan.think_s[nxt] < seconds:
            try:
                with spans.span("park"):
                    eng.park(tr.sid)
                    if trace:
                        jax.block_until_ready(eng.state)
            except (RuntimeError, ValueError, MemoryError) as e:
                fail(tr, e)
                eng.finish(tr.sid)
                return
            parked_now += 1
            heapq.heappush(followups, (t + plan.think_s[nxt], tr.plan, nxt))
        else:
            # the last turn, or the next one would fall after the window
            served[tr.plan] = list(eng.finish(tr.sid))

    t0 = time.perf_counter()
    while True:
        t = time.perf_counter() - t0
        if tw is not None:
            tw.tick(t)
        while arrivals and plans[arrivals[0]].arrival_s <= t:
            i = arrivals.popleft()
            tr = _Turn(i, 0, plans[i].arrival_s, t, plans[i].out_lens[0])
            queue.append(tr)
            turns.append(tr)
        while followups and followups[0][0] <= t:
            due, i, k = heapq.heappop(followups)
            if i in failed_plans:
                continue
            tr = _Turn(i, k, due, t, plans[i].out_lens[k])
            queue.append(tr)
            turns.append(tr)
        while queue and eng.can_admit():
            tr = queue.popleft()
            tr.admitted = time.perf_counter() - t0
            try:
                if tr.k == 0:
                    prompt = list(plans[tr.plan].prompt)
                    with spans.span("prefill"):
                        tr.sid = eng.submit(prompt)
                        if trace:
                            jax.block_until_ready(eng.state)
                    tr.times.append(time.perf_counter() - t0)
                    sid_of[tr.plan] = tr.sid
                    if trace:
                        flops_done += flops.prefill_flops(cfg, len(prompt))
                else:
                    with spans.span("resume"):
                        d = router.follow_up(sid_of[tr.plan], [])
                        if trace:
                            jax.block_until_ready(eng.state)
                    parked_now -= 1
                    if d.kind != "hit_parked" or not d.resumed:
                        raise RuntimeError(f"follow-up was routed as {d}")
                    tr.sid = d.sid
            except (RuntimeError, ValueError, MemoryError) as e:
                fail(tr, e)
                continue
            active[tr.sid] = tr
            if len(tr.times) >= tr.want:
                end_turn(tr, tr.times[-1])
        iters += 1
        parked_max = max(parked_max, parked_now)
        parked_sum += parked_now
        if active:
            if trace:
                ctx = [eng.sessions[s].prompt_len + len(eng.sessions[s].tokens)
                       - 1 for s in active]
                step_flops = flops.decode_flops(cfg, ctx)
                flops_done += step_flops
                if tw.open:
                    traced_bytes += flops.decode_bytes(cfg, ctx)
                    traced_flops += step_flops
            try:
                with spans.span("decode_step"):
                    out = eng.step()
            except (RuntimeError, ValueError, MemoryError) as e:
                for tr in list(active.values()):
                    fail(tr, e)
                    del active[tr.sid]
                    eng.finish(tr.sid)
                continue
            tt = time.perf_counter() - t0
            for sid in out:
                active[sid].times.append(tt)
            for tr in [a for a in active.values() if len(a.times) >= a.want]:
                end_turn(tr, tt)
            continue
        if t >= seconds and not queue and not followups:
            break
        if t >= seconds + DRAIN_CAP_S:
            break
        due_next = [seconds]
        if arrivals:
            due_next.append(plans[arrivals[0]].arrival_s)
        if followups:
            due_next.append(followups[0][0])
        nxt = min(due_next)
        time.sleep(min(max(0.0, nxt - (time.perf_counter() - t0)), 0.05))
    wall = time.perf_counter() - t0
    if tw is not None:
        tw.close()
    for tr in list(queue) + list(active.values()):
        fail(tr, TimeoutError("not served within the drain limit"))
    n_compiles, c_s = clock.since(c_mark)
    memory = peak_bytes(devices)

    ttft = [tr.times[0] - tr.due for tr in turns if tr.times]
    resumed = [tr.times[0] - tr.due for tr in turns if tr.times and tr.k]
    gaps = [b - a for tr in turns for a, b in zip(tr.times, tr.times[1:])]
    late = [tr.seen - tr.due for tr in turns]
    wait = [tr.admitted - tr.due for tr in turns if tr.admitted is not None]
    third = [w for tr, w in zip(turns, wait) if tr.due < seconds / 3]
    last = [w for tr, w in zip(turns, wait) if tr.due >= 2 * seconds / 3]
    counts = {"turns": len(turns), "sessions": len({tr.plan for tr in turns}),
              "sessions_finished": len(served),
              "prefills": eng.prefills - base["prefills"],
              "parks": eng.parks - base["parks"],
              "resumes": eng.resumes - base["resumes"],
              "steps": eng.steps - base["steps"],
              "parked_at_end": parked_now, "parked_max": parked_max,
              "parked_mean": parked_sum / max(iters, 1),
              "queue_at_close": len(queue), "gaps": len(gaps),
              "wait_first_third_ms": 1e3 * float(np.mean(third)) if third
              else 0.0,
              "wait_last_third_ms": 1e3 * float(np.mean(last)) if last
              else 0.0,
              "late_p99_ms": 1e3 * percentile(late, 99),
              "hbm_after_setup": hbm}
    notes = [
        f"[serve] window {seconds} s, wall {wall:.2f} s, {len(plans)} sessions "
        f"due, counts {counts}",
        f"[serve] compiles in the window: {n_compiles} ({c_s:.2f} s)",
        f"[serve] loop lateness ms p50 {percentile(late, 50) * 1e3:.3f} "
        f"p99 {percentile(late, 99) * 1e3:.3f} max "
        f"{max(late, default=0) * 1e3:.3f}",
        f"[serve] admission wait ms: first third mean "
        f"{np.mean(third) * 1e3 if third else 0:.1f}, last third mean "
        f"{np.mean(last) * 1e3 if last else 0:.1f}",
        f"[serve] ttft ms p50 {percentile(ttft, 50) * 1e3:.2f} p90 "
        f"{percentile(ttft, 90) * 1e3:.2f} (n={len(ttft)}), later turns mean "
        f"{np.mean(resumed) * 1e3 if resumed else 0:.2f} (n={len(resumed)}); "
        f"itl ms p50 "
        f"{percentile(gaps, 50) * 1e3:.2f} p99 "
        f"{percentile(gaps, 99) * 1e3:.2f} (n={len(gaps)})",
        f"[serve] memory_peak_bytes {memory}",
    ]
    spans_s = dict(spans.seconds)
    trace_red = tw.reduce() if tw is not None else None

    # the program's state goes before the reference makes its own weights
    del eng, router, weights
    gc.collect()
    t_ref = time.perf_counter()
    rng = traffic.rng_for(seed, 2)
    sample = _sample(rng, {i: v for i, v in served.items()
                           if i not in failed_plans},
                     int(mix.get("check_tokens", 400)))
    published = cfg_mod.make_published(cfg, seed)
    ref = cfg_mod.Reference(cfg, published)
    gap = max((cfg_mod.widest_gap(ref, plans[i].prompt, served[i])
               for i in sample), default=float("inf"))
    limit = float(cfg_mod.LIMITS["logit_gap"])
    control = None
    if mix.get("control"):
        # the control in the program's place: the reference in the next
        # precision down chooses the tokens
        low = cfg_mod.Reference(cfg, published, fp8=True)
        control = max((cfg_mod.control_gap(ref, low, plans[i].prompt,
                                           served[i]) for i in sample),
                      default=float("inf"))
        notes.append(f"[serve] sound logit_gap {gap!r}, control {control!r}")
    judged = gap if control is None else control
    toks = [t for i in sample for t in served[i]]
    notes.append(f"[serve] checked tokens: {len(set(toks))} distinct of "
                 f"{len(toks)}")
    notes.append(f"[serve] reference over {len(sample)} sessions, "
                 f"{sum(len(served[i]) for i in sample)} served tokens, "
                 f"{time.perf_counter() - t_ref:.2f} s")
    readings = Readings(
        cell=cell, cfg=cfg, mix=mix, peaks=peaks, spans=spans_s,
        trace=trace_red,
        extra={"counts": counts, "wall_s": wall, "flops": flops_done,
               "traced_decode_bytes": traced_bytes,
               "traced_decode_flops": traced_flops, "sound_gap": gap,
               "control_gap": control})
    e2e = {"setup_s": setup_s,
           "itl_mean_ms": sum(gaps) / len(gaps) * 1e3 if gaps else math.nan}
    return CellRun(attempted=len(turns), failed=n_failed, e2e=e2e,
                   checks={"logit_gap": (judged, limit)},
                   correct=bool(judged <= limit), memory_peak_bytes=memory,
                   readings=readings, notes=notes)
