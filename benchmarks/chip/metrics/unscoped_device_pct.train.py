"""unscoped_device_pct.train: the share of the traced window's device busy
time whose ops carry none of the program's scopes (``yardstick.scopes``)."""
from yardstick import scopes


def read(out, cell):
    got = scopes.of_run(out, cell, "unscoped_device_pct.train")
    if got is None or got["busy_s"] <= 0:
        return None
    return 100.0 * got["unscoped_s"] / got["busy_s"]
