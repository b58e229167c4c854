"""Host milliseconds of one write of a session's KV slice into the engine's
pooled state: the program's ``engine.write_slot`` spans, each ended when the
write is done on the device, over their count, over the spans recorded while
the profiler ran. The serving loop is one thread that waits for each prefill
and step, so the span is the write's dispatch and its device time."""


def read(r):
    try:
        from repro import obs
    except ImportError:          # a program that records no spans of its own
        return None
    s = obs.summary()["spans"].get("engine.write_slot")
    return 1e3 * s["total_s"] / s["count"] if s else None
