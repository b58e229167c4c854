"""Share (%) of a mixture-of-experts decode step's roofline: the least time
the chip could take for a mean traced decode step (the larger of the bytes
it needs over HBM bandwidth and the operations it needs over peak compute)
over the mean device time inside the traced decode steps' spans.

What a step needs comes from the program's counters while the profiler ran
(``engine.decode_steps``, ``engine.decode_tokens``,
``engine.kv_tokens_live``, ``moe.experts_touched``, ``moe.tokens_held``),
taken per step, with sizes from the configuration's ``step_need``: every
weight the step must read, the held experts the routing touched, and the
live latent cache. None for a program without those counters."""

from pathlib import Path


def read(r):
    if r.trace is None or not r.peaks:
        return None
    try:
        from repro import obs
    except ImportError:          # a program that records no counters
        return None
    c = obs.summary()["counters"]
    steps = c.get("engine.decode_steps", 0)
    if not steps or "moe.experts_touched" not in c:
        return None
    device_s = r.trace["span_device_s"].get("bench.decode_step", 0.0)
    n = r.trace["span_count"].get("bench.decode_step", 0)
    if device_s <= 0 or not n:
        return None
    from bench import harness

    mod = harness.load_module(Path(harness.HERE) / "configs"
                              / f"{r.cfg['name']}.py")
    need_b, need_f = mod.step_need(
        r.cfg, tokens=c.get("engine.decode_tokens", 0) / steps,
        cached=c.get("engine.kv_tokens_live", 0) / steps,
        touched=c["moe.experts_touched"] / steps,
        held_picks=c.get("moe.tokens_held", 0) / steps)
    t_min = max(need_b / r.peaks["hbm_bytes_per_s"],
                need_f / r.peaks["bf16_flops_per_s"])
    return 100.0 * t_min / (device_s / n)
