"""Share of the profiled window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""


def read(ctx):
    dev = ctx["device_trace"]
    if not dev["ops"] or dev["window_s"] <= 0:
        return None
    return 1.0 - dev["busy_s"] / dev["window_s"]
