"""Share of the traced window in which no operation ran on the device:
1 - the union of the device's operation intervals over the window."""


def read(rec):
    t = rec.trace
    if t is None or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
