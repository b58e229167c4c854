"""Chip smoke test: drive the system's main paths once on a TPU and check them.

    python chip_smoke.py              # one chip: serving, kernels, executor
    python chip_smoke.py --chips 4    # four chips: sharded training only

One process runs every phase and holds the chip throughout. Progress and
diagnostics go to earlier lines; the last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only when every phase passed. Any failure (no TPU, a check
out of bounds, an exception) exits non-zero without it. Times, rates and
memory printed here are smoke figures from one run, not benchmark numbers.

Phases on one chip:

* serving — granite-3-2b at full published width and depth (bf16, random
  weights from ``--seed``) through ``Router`` -> ``ServingEngine`` ->
  ``LocStore``: 8 prompts of 128-1024 tokens, 32 new tokens each; one session
  is parked and resumed and must continue exactly as a control session with
  the same prompt that never parked; the logits decode produced through the
  KV cache are compared with a cache-free forward pass over prompt+generated.
* kernels — the Pallas flash and decode attention kernels, compiled for the
  chip (not interpreted), against ``kernels/ref.py`` at granite-3-2b head
  geometry; decode on a full-size (40, 8, 4096, 512) pool, called as the
  model's decode step calls it.
* executor — the 64-map/8-reduce workflow over 2 GiB of inputs with jitted
  task bodies, through ``compile_workflow`` -> ``ProactiveScheduler`` ->
  ``WorkflowExecutor`` with every node mapped to the chip; outputs must match
  a serial run of the same bodies in topological order.

The serving and kernel comparisons also read planted faults (a stale or lost
cache entry, another session's cache; a skipped k-block, an ignored mask or
length, a left-out new token) and fail unless each of them lies outside the
bound.

With ``--chips 4``: a few steps of full-width, full-depth granite-3-2b
training on a 2x2 (data, model) mesh, after the same steps at 2 layers have
agreed between the mesh and a single device. At the small step size used,
the 2-layer loss must fall from the first step on, on both.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import TRAIN_4K, get_config  # noqa: E402
from repro.core import (ProactiveScheduler, WorkflowExecutor,  # noqa: E402
                        compile_workflow)
from repro.core.config import ServingConfig  # noqa: E402
from repro.core.locstore import LocStore, tiered_hierarchy  # noqa: E402
from repro.core.workloads import mapreduce_workflow  # noqa: E402
from repro.data.pipeline import SyntheticCorpus  # noqa: E402
from repro.dist.hints import sharding_rules  # noqa: E402
from repro.dist.mesh import make_host_mesh  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels import decode_attention as dec_k  # noqa: E402
from repro.kernels import flash_attention as flash_k  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import forward_logits, init_params  # noqa: E402
from repro.serve.engine import (JaxComputeBackend, Router,  # noqa: E402
                                ServingEngine, _cache_name)
from repro.train.optimizer import OptConfig, init_opt_state  # noqa: E402
from repro.train.train_step import (make_train_step,  # noqa: E402
                                    microbatches_for, sharded_train_step)

# Bounds. Each check also reads planted faults at the same sizes (a stale or
# lost cache entry, another session's cache; a skipped k-block, an ignored
# mask or length) and fails unless every one of them is out of bounds.
# Cached decode and the cache-free forward round the bf16 residual stream
# differently, and the difference grows with depth: 0.7% of max |logit| at 2
# layers on the CPU, 1.7% at 40 on a TPU v5e. There one stale entry among
# 699-891 reads 3.7-4.9%, so the bound sits between the two, with equal
# ratios on each side.
LOGIT_REL_TOL = 0.025         # max |decode - forward| / max |forward|
# A bf16 kernel output is one rounding (2^-8 relative) from the reference,
# plus the f32 accumulation's own; bounded per row (see row_rel_err).
KERNEL_REL_TOL = 0.03
LOSS_REL_TOL = 1e-3           # loss, 2x2 mesh vs one device, per step
GNORM_REL_TOL = 1e-2          # grad norm, 2x2 mesh vs one device, per step

GiB = 1 << 30


def log(msg: str) -> None:
    print(msg, flush=True)


class Failed(Exception):
    """A phase's check is out of bounds."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


class CompileClock:
    """Backend compile seconds, read from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds.append(duration)

    def since(self, mark: int) -> tuple[int, float]:
        new = self.seconds[mark:]
        return len(new), sum(new)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


# ---------------------------------------------------------------- serving
class RecordingBackend(JaxComputeBackend):
    """The engine's JAX backend, keeping the last decode step's logits."""

    last_logits: jax.Array | None = None

    def decode(self, params, state, tokens):
        logits, state = self._decode(params, state, jnp.asarray(tokens))
        self.last_logits = logits[:, -1]
        return np.asarray(jnp.argmax(self.last_logits, axis=-1)), state


def phase_serving(cfg, seed: int, clock: CompileClock, *,
                  n_requests: int = 8, new_tokens: int = 32,
                  prompt_lens: tuple[int, int] = (128, 1024),
                  max_seq: int = 4096) -> None:
    sc = ServingConfig(max_batch=8, max_seq=max_seq)
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[serving] {cfg.name}: {n_params / 1e9:.3f} B params, "
        f"{cfg.n_layers} layers, d_model {cfg.d_model}, dtype {cfg.dtype}")
    backend = RecordingBackend(cfg, sc.max_seq)
    store = LocStore(1, hierarchy=tiered_hierarchy())
    eng = ServingEngine(cfg, params, config=sc, node=0, store=store,
                        backend=backend)
    router = Router([eng], store, config=sc)

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n_requests - 1)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in lens]
    prompts.append(list(prompts[0]))      # control: never parked
    mark = len(clock.seconds)
    t0 = time.perf_counter()
    sids = []
    for p in prompts:
        d = router.route()
        sids.append(d.engine.submit(p))
    n_c, c_s = clock.since(mark)
    log(f"[serving] prefill of {len(prompts)} prompts "
        f"(lengths {sorted(len(p) for p in prompts)}): "
        f"{time.perf_counter() - t0:.2f} s wall, {n_c} compiles "
        f"{c_s:.2f} s")
    parked, control, other = sids[0], sids[-1], sids[1]
    tracked: dict[int, list[np.ndarray]] = {parked: [], other: []}
    step_s: list[float] = []
    step_tokens: list[int] = []

    def step() -> None:
        slots = {s: eng.sessions[s].slot for s in tracked
                 if eng.sessions[s].slot is not None}
        live = sum(1 for s in eng.sessions.values()
                   if s.slot is not None and not s.done)
        t = time.perf_counter()
        eng.step()
        step_s.append(time.perf_counter() - t)
        step_tokens.append(live)
        for s, slot in slots.items():
            tracked[s].append(np.asarray(backend.last_logits[slot],
                                         np.float32)[:cfg.vocab])
        for s in sids:
            sess = eng.sessions[s]
            if not sess.done and len(sess.tokens) >= new_tokens:
                eng.finish(s)

    mark = len(clock.seconds)
    for _ in range(new_tokens // 4):
        step()
    eng.park(parked)
    check(eng.sessions[parked].slot is None
          and store.stat(_cache_name(parked)).tier_on(0) == "bb",
          "parked session's KV slice is not in the burst-buffer tier")
    for _ in range(4):
        step()
    d = router.follow_up(parked, prompts[0] + eng.sessions[parked].tokens)
    check(d.kind == "hit_parked" and d.resumed and not d.prefilled,
          f"resume of the parked session was {d}")
    while any(not eng.sessions[s].done for s in sids):
        step()
    n_c, c_s = clock.since(mark)
    log(f"[serving] decode: {len(step_s)} steps, {n_c} compiles {c_s:.2f} s, "
        f"first step {step_s[0]:.2f} s")
    warm = slice(1, None)
    tok_s = sum(step_tokens[warm]) / sum(step_s[warm])
    log(f"[serving] decode smoke figure (not a benchmark): "
        f"{tok_s:.1f} tokens/s over {len(step_s) - 1} steps at "
        f"<= {sc.max_batch} live sessions, "
        f"median step {np.median(step_s[warm]) * 1e3:.2f} ms")

    a, c = eng.sessions[parked].tokens, eng.sessions[control].tokens
    log(f"[serving] park/resume: parked session {len(a)} tokens, control "
        f"{len(c)} tokens, identical={a == c}")
    check(len(a) == len(c) == new_tokens and a == c,
          "parked-and-resumed session diverged from its control")

    fwd = jax.jit(lambda p, t: forward_logits(cfg, p, t))

    def forward(context: list[int], gen: list[int]) -> np.ndarray:
        """Cache-free logits at the positions that produced ``gen``."""
        seq = jnp.asarray([context + gen], jnp.int32)
        return np.asarray(fwd(params, seq)[0, len(context):, :cfg.vocab],
                          np.float32)

    mark = len(clock.seconds)
    for sid, rows in tracked.items():
        sess = eng.sessions[sid]
        prompt = prompts[sids.index(sid)]
        n = len(rows)                 # decode steps that produced tokens[1:]
        check(n == len(sess.tokens) - 1, "missing decode logits")
        gen = sess.tokens[:n]
        want = forward(prompt, gen)
        got = np.stack(rows)
        err = rel_err(got, want)
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        log(f"[serving] reference, session {sid} (prompt {len(prompt)}): "
            f"max|decode - forward| = {np.abs(got - want).max():.4f}, "
            f"max|forward| = {np.abs(want).max():.4f}, relative "
            f"{err:.5f} (bound {LOGIT_REL_TOL}), argmax agreement "
            f"{agree:.3f} over {n} positions")
        check(err <= LOGIT_REL_TOL, f"session {sid} logits off by {err}")
        # Planted faults: the logits a decode would give through a wrong
        # cache, read the way the check above reads the real decode. Each
        # must fail the bound, or the check could not see that fault.
        peer = other if sid == parked else parked
        mid = len(prompt) // 2
        stale = (prompt[:mid] + [(prompt[mid] + 1) % cfg.vocab]
                 + prompt[mid + 1:])
        faults = {"stale entry": stale,           # one slot holds a wrong K/V
                  "first entry lost": prompt[1:],  # RoPE: only the set shrinks
                  "other slot": prompts[sids.index(peer)]}  # another's cache
        for name, context in faults.items():
            f_err = rel_err(forward(context, gen), want)
            log(f"[serving] planted fault '{name}', session {sid}: relative "
                f"{f_err:.5f} (must exceed {LOGIT_REL_TOL})")
            check(f_err > LOGIT_REL_TOL,
                  f"the check cannot see a '{name}' fault ({f_err})")
    n_c, c_s = clock.since(mark)
    log(f"[serving] reference forward: {n_c} compiles {c_s:.2f} s")
    log(f"[serving] peak_bytes_in_use {peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------- kernels
def abs_err(got, want) -> float:
    return float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max())


def row_rel_err(got, want) -> float:
    """Max over rows (one head's hd-vector at one query) of
    max|got - want| / max|want| in that row. An attention output is a
    softmax average of v, so its scale differs by orders of magnitude between
    a query with few keys and one with thousands; a bound on the whole
    array's max would be set by the few-key rows alone."""
    g = np.asarray(jnp.asarray(got, jnp.float32))
    w = np.asarray(jnp.asarray(want, jnp.float32))
    scale = np.maximum(np.abs(w).max(axis=-1, keepdims=True), 1e-30)
    return float((np.abs(g - w) / scale).max())


def check_kernel(name: str, err: float, faults: dict[str, float]) -> None:
    """``err`` within the bound; each planted fault's reading (the reference
    computed with that fault, read as the kernel is) outside it."""
    log(f"[kernels] {name}: per-row relative error {err:.5f} "
        f"(bound {KERNEL_REL_TOL})")
    check(err <= KERNEL_REL_TOL, f"{name} error {err}")
    for fault, f_err in faults.items():
        log(f"[kernels] {name} planted fault '{fault}': per-row relative "
            f"{f_err:.5f} (must exceed {KERNEL_REL_TOL})")
        check(f_err > KERNEL_REL_TOL,
              f"the {name} check cannot see a '{fault}' fault ({f_err})")


def phase_kernels(seed: int, clock: CompileClock, *, B: int = 2,
                  S: int = 2048, Hq: int = 32, Hkv: int = 8, hd: int = 64,
                  cache: int = 4096, decode_batch: int = 8,
                  n_layers: int = 40) -> None:
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(bf)

    q, k, v = (normal(ks[0], (B, S, Hq, hd)), normal(ks[1], (B, S, Hkv, hd)),
               normal(ks[2], (B, S, Hkv, hd)))
    mark = len(clock.seconds)
    got = flash_k.flash_attention(q, k, v, causal=True, interpret=False)
    n_c, c_s = clock.since(mark)
    bk = flash_k.DEFAULT_BK
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(q, k, v, causal=True)
        # queries past the first k-block, without it: the causal mask only
        # compares positions, so dropping block 0 is shifting both q and k
        skip = ref.flash_attention_ref(q[:, bk:], k[:, bk:], v[:, bk:],
                                       causal=True)
        faults = {"first k-block skipped": row_rel_err(skip, want[:, bk:]),
                  "causal mask ignored": row_rel_err(
                      ref.flash_attention_ref(q, k, v, causal=False), want)}
    log(f"[kernels] flash_attention B={B} S={S} Hq={Hq} Hkv={Hkv} hd={hd} "
        f"bf16 causal: max abs err {abs_err(got, want):.5f}; "
        f"{n_c} compiles {c_s:.2f} s")
    check_kernel("flash_attention", row_rel_err(got, want), faults)

    # decode: the kernel on the pooled (L, B, S, Hkv*hd) cache, called as
    # the model's decode step calls it, for one traced layer of the pool
    C = Hkv * hd
    kd = jax.random.split(ks[3], 5)
    qd = normal(kd[0], (decode_batch, Hq, hd))
    kp, vp = (normal(kd[1], (n_layers, decode_batch, cache, C)),
              normal(kd[2], (n_layers, decode_batch, cache, C)))
    kn, vn = (normal(kd[3], (decode_batch, C)),
              normal(kd[4], (decode_batch, C)))
    lengths = np.random.default_rng(seed).integers(1, cache, decode_batch)
    lengths[0], lengths[1] = cache - 1, 0       # full, and an idle slot
    lengths = jnp.asarray(lengths, jnp.int32)
    layer = jnp.int32(n_layers // 2)
    decode = jax.jit(lambda *a: dec_k.decode_attention(*a, interpret=False))
    mark = len(clock.seconds)
    got = decode(qd, kp, vp, kn, vn, lengths, layer, jnp.int32(0))
    n_c, c_s = clock.since(mark)
    bk = dec_k.block_size(cache)
    bidx = jnp.arange(decode_batch)

    def cache_with_token(at):
        return [p[layer].at[bidx, at].set(new).reshape(decode_batch, cache,
                                                       Hkv, hd)
                for p, new in ((kp, kn), (vp, vn))]

    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(qd, *cache_with_token(lengths),
                                        lengths + 1)
        # the last live block skipped: the token sits where it starts
        start = jnp.where(lengths > 0, (lengths - 1) // bk * bk, 0)
        faults = {
            "lengths ignored": row_rel_err(ref.decode_attention_ref(
                qd, *cache_with_token(lengths),
                jnp.full_like(lengths, cache)), want),
            "last k-block skipped": row_rel_err(ref.decode_attention_ref(
                qd, *cache_with_token(start), start + 1), want),
            "new token left out": row_rel_err(ref.decode_attention_ref(
                qd, kp[layer].reshape(decode_batch, cache, Hkv, hd),
                vp[layer].reshape(decode_batch, cache, Hkv, hd),
                jnp.maximum(lengths, 1)), want)}
    log(f"[kernels] decode_attention pool L={n_layers} B={decode_batch} "
        f"S={cache} Hq={Hq} Hkv={Hkv} hd={hd} bk={bk} bf16: max abs err "
        f"{abs_err(got, want):.5f}; {n_c} compiles {c_s:.2f} s")
    check_kernel("decode_attention", row_rel_err(got, want), faults)

    # the same call with a traced window, as a localglobal model's local
    # layers make it (a quarter of the cache: gemma3's 1024 at 4096); most
    # slots' lengths pass it, so the kernel reads from the window's first
    # block to the last live one
    window = cache // 4
    got = decode(qd, kp, vp, kn, vn, lengths, layer, jnp.int32(window))
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(qd, *cache_with_token(lengths),
                                        lengths + 1, window=window)
        faults = {"window ignored": row_rel_err(ref.decode_attention_ref(
            qd, *cache_with_token(lengths), lengths + 1), want)}
    log(f"[kernels] decode_attention window={window}: "
        f"{int((lengths > window).sum())} of {decode_batch} slots past it, "
        f"max abs err {abs_err(got, want):.5f}")
    check_kernel("decode_attention window", row_rel_err(got, want), faults)


# --------------------------------------------------------------- executor
def mapreduce_bodies(g, n_map: int, n_reduce: int) -> None:
    """Jitted jnp bodies for ``mapreduce_workflow``'s tasks."""

    @jax.jit
    def map_body(x):                       # (n,) -> (n_reduce, 4096)
        y = jnp.tanh(x.reshape(n_reduce, -1, 4096))
        return (y * y).sum(axis=1)

    reduce_body = jax.jit(lambda xs: jnp.stack(xs).sum(axis=0))
    collect_body = jax.jit(lambda xs: jnp.stack(xs))

    def map_fn(i):
        def fn(**kw):
            m = map_body(kw[f"shard{i}"])
            return {f"m{i}_r{j}": m[j] for j in range(n_reduce)}
        return fn

    def reduce_fn(j):
        def fn(**kw):
            xs = tuple(kw[f"m{i}_r{j}"] for i in range(n_map))
            return {f"out{j}": reduce_body(xs)}
        return fn

    for i in range(n_map):
        g.tasks[f"map{i}"].fn = map_fn(i)
    for j in range(n_reduce):
        g.tasks[f"reduce{j}"].fn = reduce_fn(j)
    g.tasks["collect"].fn = lambda **kw: {"final": collect_body(
        tuple(kw[f"out{j}"] for j in range(n_reduce)))}


def phase_executor(seed: int, clock: CompileClock, *,
                   total_bytes: int = 2 * GiB, n_map: int = 64,
                   n_reduce: int = 8) -> None:
    shard_elems = total_bytes // 4 // n_map
    t0 = time.perf_counter()
    data = np.random.default_rng(seed).standard_normal(
        (n_map, shard_elems), np.float32)
    inputs = {f"shard{i}": data[i] for i in range(n_map)}
    log(f"[executor] inputs: {n_map} shards, {data.nbytes / GiB:.3f} GiB, "
        f"made in {time.perf_counter() - t0:.2f} s")
    g = mapreduce_workflow(n_map, n_reduce, shard_bytes=float(shard_elems * 4))
    mapreduce_bodies(g, n_map, n_reduce)
    wf = compile_workflow(g)
    chip = jax.devices()[0]
    mark = len(clock.seconds)
    ex = WorkflowExecutor(wf, ProactiveScheduler(wf), n_nodes=4,
                          hierarchy=tiered_hierarchy(),
                          device_of=lambda node: chip, inject_inputs=inputs)
    res = ex.run()
    got = np.asarray(res.outputs["final"])
    n_c, c_s = clock.since(mark)
    rep = ex.prefetch.report()
    log(f"[executor] run: {res.wall_seconds:.2f} s wall (smoke figure), "
        f"{n_c} compiles {c_s:.2f} s, {len(res.task_records)} tasks, "
        f"prefetch device_puts {int(rep['device_puts'])}, "
        f"bytes prefetched {rep['bytes_prefetched']:.0f}, "
        f"locality hit rate {res.locality_hit_rate:.3f}")

    values = dict(inputs)
    for tid in g.topo_order():
        t = g.tasks[tid]
        values.update(t.fn(**{n: values[n] for n in t.inputs}))
    want = np.asarray(values["final"])
    diff = float(np.abs(got - want).max())
    log(f"[executor] serial reference: shape {want.shape}, max abs diff "
        f"{diff} (bound 0), finite={bool(np.isfinite(got).all())}")
    check(got.shape == want.shape == (n_reduce, 4096), "output shape")
    check(np.isfinite(got).all() and diff == 0.0,
          "executor output differs from the serial run")
    check(rep["device_puts"] >= 1, "prefetch made no device copy")
    log(f"[executor] peak_bytes_in_use {peak_bytes(chip)}")


# --------------------------------------------------------------- 4 chips
def falls(losses: list[float]) -> bool:
    """Every loss after the first step's is below it."""
    return all(x < losses[0] for x in losses[1:])


def phase_train4(cfg, seed: int, clock: CompileClock, *, batch: int = 8,
                 seq: int = 2048, steps: int = 3, ref_layers: int = 2) -> None:
    mesh = make_host_mesh()
    check(dict(mesh.shape) == {"data": 2, "model": 2},
          f"expected a 2x2 mesh, got {dict(mesh.shape)}")
    devs = list(mesh.devices.flat)
    # AdamW's first update moves every weight by about lr whatever its
    # gradient's size. At the usual 3e-4 that overshoots from a random init
    # and the loss rises for a few steps (on the CPU at 2 layers, in f32 as in
    # bf16). At a constant 1e-5 the 2-layer loss falls from the first update
    # on, so a rise there is an update that goes the wrong way or lands on
    # the wrong shard; the full-depth step runs the same update code. At 40
    # layers on a TPU v5e the loss still rose at 1e-5, even on one batch
    # repeated, and fell at 1e-6: the step is too long there, so only a
    # finite loss is required at full depth.
    oc = OptConfig(lr=1e-5, warmup_steps=1, total_steps=1 << 20)
    mb, acc = microbatches_for(cfg, TRAIN_4K)
    it = SyntheticCorpus(cfg.vocab, seed=seed).batches(batch, seq)
    batches = [next(it) for _ in range(steps)]
    log(f"[train4] mesh {dict(mesh.shape)} over {[d.id for d in devs]}; "
        f"batch {batch} x seq {seq}, {mb} microbatches, lr {oc.lr}")

    def fresh(c, shardings=None):
        """Params and AdamW state of ``c`` from the seed, created directly
        in ``shardings`` (the default device when None)."""
        def init():
            p = init_params(c, jax.random.PRNGKey(seed))
            return p, init_opt_state(oc, p)
        return jax.jit(init, out_shardings=shardings)()

    # full width, reduced depth: every step on the mesh must agree with the
    # same step on one device, each after the same AdamW updates
    cfg_r = dataclasses.replace(cfg, n_layers=ref_layers)
    step_r, (p_sh, o_sh, _) = sharded_train_step(
        cfg_r, oc, mesh, batches[0], microbatches=mb, accum_dtype=acc)
    single = jax.jit(make_train_step(cfg_r, oc, microbatches=mb,
                                     accum_dtype=acc), donate_argnums=(0, 1))
    runs = {"2x2 mesh": (step_r, lambda: sharding_rules(mesh), (p_sh, o_sh)),
            "one device": (single, contextlib.nullcontext, None)}
    metrics: dict[str, list[tuple[float, float]]] = {}
    mark = len(clock.seconds)
    for name, (fn, rules, shardings) in runs.items():
        p, o = fresh(cfg_r, shardings)
        metrics[name] = []
        with rules():
            for b in batches:
                p, o, m = fn(p, o, b)
                metrics[name].append((float(m["loss"]), float(m["grad_norm"])))
        del p, o
    del runs, single, step_r
    gc.collect()
    n_c, c_s = clock.since(mark)
    for i, ((l4, g4), (l1, g1)) in enumerate(zip(metrics["2x2 mesh"],
                                                 metrics["one device"])):
        dl, dg = abs(l4 - l1) / abs(l1), abs(g4 - g1) / abs(g1)
        log(f"[train4] {ref_layers}-layer step {i + 1}: one device loss "
            f"{l1:.6f} grad_norm {g1:.6f}; 2x2 mesh loss {l4:.6f} grad_norm "
            f"{g4:.6f}; relative diff loss {dl:.2e} (bound {LOSS_REL_TOL}) "
            f"grad_norm {dg:.2e} (bound {GNORM_REL_TOL})")
        check(dl <= LOSS_REL_TOL and dg <= GNORM_REL_TOL,
              f"sharded step {i + 1} disagrees with the single-device step")
    for name, ms in metrics.items():
        check(falls([x for x, _ in ms]),
              f"{ref_layers}-layer loss on {name} does not fall: {ms}")
    log(f"[train4] {ref_layers}-layer steps: loss falls on both; {n_c} "
        f"compiles {c_s:.2f} s")

    # full width, full depth on the mesh
    step, (p_sh, o_sh, _) = sharded_train_step(
        cfg, oc, mesh, batches[0], microbatches=mb, accum_dtype=acc)

    params, opt = fresh(cfg, (p_sh, o_sh))
    leaves = jax.tree.leaves((params, opt["m"], opt["v"]))
    spread = [len({s.device for s in x.addressable_shards}) for x in leaves]
    split = sum(not x.sharding.is_fully_replicated for x in leaves)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[train4] {cfg.name}: {n_params / 1e9:.3f} B params, "
        f"{cfg.n_layers} layers; {len(leaves)} param+moment arrays, "
        f"{split} partitioned, the rest replicated; every array on "
        f"{min(spread)}..{max(spread)} devices")
    check(min(spread) == 4, "an array does not span all four devices")
    mark = len(clock.seconds)
    losses = []
    with sharding_rules(mesh):
        for i, b in enumerate(batches):
            t = time.perf_counter()
            params, opt, m = step(params, opt, b)
            losses.append(float(m["loss"]))
            log(f"[train4] step {i + 1}: loss {losses[-1]:.5f} grad_norm "
                f"{float(m['grad_norm']):.5f} ({time.perf_counter() - t:.2f} "
                f"s wall, smoke figure)")
            check(bool(np.isfinite(losses[-1])), "loss is not finite")
    n_c, c_s = clock.since(mark)
    log(f"[train4] full-depth steps: {n_c} compiles {c_s:.2f} s")
    log(f"[train4] peak_bytes_in_use per device "
        f"{[peak_bytes(d) for d in devs]}")


# ------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {devs[0].platform} devices")
        return 1
    if len(devs) < args.chips:
        log(f"--chips {args.chips}: only {len(devs)} devices")
        return 1
    log(f"devices: {len(devs)} x {devs[0].device_kind}; "
        f"jax {jax.__version__}; compile cache {use_compile_cache()}")
    clock = CompileClock()
    cfg = get_config("granite-3-2b")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_train4(cfg, args.seed, clock)
        else:
            phase_serving(cfg, args.seed, clock)
            gc.collect()
            phase_kernels(args.seed, clock)
            phase_executor(args.seed, clock)
    except Failed as e:
        log(f"FAILED: {e}")
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
