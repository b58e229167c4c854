"""Host milliseconds per task spent in ``LocStore`` calls: the program's
outermost ``store.*`` spans (a store call inside another counts once), on
every thread, over the count of ``task`` spans, over the spans recorded
while the profiler ran."""


def read(r):
    try:
        from repro import obs
    except ImportError:          # a program that records no spans of its own
        return None
    s = obs.summary()
    tasks, store = s["spans"].get("task"), s["layers"].get("store")
    if not tasks:
        return None
    return 1e3 * (store["total_s"] if store else 0.0) / tasks["count"]
