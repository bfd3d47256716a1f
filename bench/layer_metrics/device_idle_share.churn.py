"""Device: share of the traced window in which no op ran on the chip."""


def read(r: dict):
    tr = r.get("trace")
    if tr is None or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
