"""device_idle_pct.train: the share of the traced window in which no
operation ran on the chip, while training (steps traced after the
measured window; ``yardstick.trace``)."""


def read(out, cell):
    t = out.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
