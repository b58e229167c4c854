"""Seconds of the compile events (compiles and loads from the persistent
compile cache) that the program's counter saw before the profiler's first
recorded span: those of set-up, as the window compiles nothing (the loops
print its compiles). Compiles after the window, the reference's, are left
out."""


def read(r):
    try:
        from repro import obs
    except ImportError:          # a program without its own compile counter
        return None
    start = obs.summary()["start_s"]
    if start is None:
        return None
    c = obs.compiles(before=start)
    return sum(v["seconds"] for v in c.values()) if c else None
