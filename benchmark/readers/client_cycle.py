"""One request's round trip as the load generator saw it, from its records
as they are (harness.RECORD: `sent` and `done` in ms from the window's
open).  Taken over the requests sent in the untraced part of the window
where the run has one (the seconds the program's own clocks are read over),
else over the window.

The value is the mean of done - sent over the answered ones, in ms.  The
generator stamps `sent` when it queues a request and `done` when it has read
the answer, so its own write and read lag lie inside the round trip: less
the server's residence (fe_residence_ms) it is what the two socket queues
and the generator hold together, not the generator's share alone.
"""

import numpy as np


def read(ctx):
    records = ctx["records"]
    span_ms = 1e3 * min(ctx.get("untraced_s") or ctx["seconds"], ctx["seconds"])
    sent = records["sent"].astype(np.float64)
    done = records["done"].astype(np.float64)
    mine = (sent >= 0) & (sent < span_ms) & ~np.isnan(done)
    if not mine.any():
        return None
    return float((done[mine] - sent[mine]).mean())
