"""Share (%) of task-input bytes handed to a body as the prefetch engine's
device copy, over all task-input bytes, over the window."""


def read(r):
    total = r.extra.get("input_bytes", 0.0)
    if total <= 0:
        return None
    return 100.0 * r.extra.get("prefetched_bytes", 0.0) / total
