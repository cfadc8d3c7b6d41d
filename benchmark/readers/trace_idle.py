"""The device's idle share of the traced window, in percent: 1 minus the
union of the intervals in which an operation ran, over the window."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
