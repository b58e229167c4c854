"""Share (%) of the decode step's roofline: the least time the chip could
take for the traced decode steps (the larger of the bytes they need over HBM
bandwidth and the operations they need over peak compute; bytes: the
weights and the live sequences' keys and values, from ``bench/flops.py``)
over the device time inside those steps' spans in the trace."""


def read(r):
    if r.trace is None:
        return None
    device_s = r.trace["span_device_s"].get("bench.decode_step", 0.0)
    need_b = r.extra.get("traced_decode_bytes", 0.0)
    need_f = r.extra.get("traced_decode_flops", 0.0)
    if device_s <= 0 or need_b <= 0:
        return None
    t_min = max(need_b / r.peaks["hbm_bytes_per_s"],
                need_f / r.peaks["bf16_flops_per_s"])
    return 100.0 * t_min / device_s
