"""Share (%) of task time spent waiting for inputs: the executor's
``io_wait_total`` over io_wait plus the bodies' run time, over the window."""


def read(r):
    wait, run = r.extra.get("io_wait_s", 0.0), r.extra.get("run_s", 0.0)
    return 100.0 * wait / (wait + run) if wait + run > 0 else None
