"""Mean host milliseconds of one ``ServingEngine.step`` outside its wait for
the step's tokens: the self time of the program's ``engine.step`` span (its
time less that of its ``engine.step.sync`` child) per step, over the spans
recorded while the profiler ran."""


def read(r):
    try:
        from repro import obs
    except ImportError:          # a program that records no spans of its own
        return None
    s = obs.summary()["spans"].get("engine.step")
    return 1e3 * s["self_s"] / s["count"] if s else None
