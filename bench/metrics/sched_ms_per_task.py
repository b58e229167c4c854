"""Host milliseconds per task spent in ``ProactiveScheduler.select`` and
``preplace``, over the workflows of the window."""


def read(r):
    s, n = r.spans.get("schedule"), r.extra.get("tasks", 0)
    return 1e3 * sum(s) / n if s and n else None
