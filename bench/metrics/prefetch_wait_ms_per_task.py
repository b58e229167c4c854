"""Host milliseconds per task that a task's stage-in waited for a prefetch
still in flight: the program's ``prefetch.wait`` spans over the count of
``task`` spans, over the spans recorded while the profiler ran."""


def read(r):
    try:
        from repro import obs
    except ImportError:          # a program that records no spans of its own
        return None
    s = obs.summary()["spans"]
    tasks, wait = s.get("task"), s.get("prefetch.wait")
    if not tasks:
        return None
    return 1e3 * (wait["total_s"] if wait else 0.0) / tasks["count"]
