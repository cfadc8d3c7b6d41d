"""Right answers received inside the window, per second of the window: all
the work over all the time.  An answer that came after the close, a wrong
one and one that never came do not count."""


def read(ctx):
    done = ctx["records"]["done"]
    got = ctx["right"] & (done >= 0) & (done < ctx["seconds"] * 1e3)
    return float(got.sum()) / ctx["seconds"]
