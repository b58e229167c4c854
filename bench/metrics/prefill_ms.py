"""Mean host milliseconds of one ``ServingEngine.submit`` (prefill and the
slot write, ended when the pool is ready), over the window."""


def read(r):
    s = r.spans.get("prefill")
    return 1e3 * sum(s) / len(s) if s else None
