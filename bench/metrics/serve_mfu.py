"""Share (%) of the chip's peak bf16 rate: the model operations of every
prompt prefilled and every token decoded in the window (from
``bench/flops.py``) over the window's wall time times the peak."""


def read(r):
    done, wall = r.extra.get("flops", 0.0), r.extra.get("wall_s", 0.0)
    if done <= 0 or wall <= 0 or not r.peaks:
        return None
    return 100.0 * done / (wall * r.peaks["bf16_flops_per_s"])
