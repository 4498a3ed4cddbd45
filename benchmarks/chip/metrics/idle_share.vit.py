"""Share of the traced window in which no operation ran on the device, in %.
1 - (union of device op intervals) / window; ``chipbench/trace.py``."""


def read(rec):
    t = rec["trace"]
    if not t["window_s"] or not t["device_events"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
