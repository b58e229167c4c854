"""Mean host milliseconds of one ``ServingEngine.step`` (one token for every
slotted session, ended when the tokens reach the host), over the window."""


def read(r):
    s = r.spans.get("decode_step")
    return 1e3 * sum(s) / len(s) if s else None
