"""The loop clock of the C++ front end's one epoll thread: the cumulative
tables /debug/vars native_frontend.front.phases and .rows, {row: {count,
sum_ns, max_ns}}, taken as the difference between the two scrapes of the
untraced part of the window (vars0, vars1), as the stage clock's is.  The
thread's own phases (`phases`; `rows` holds `turn` and the per-request
`req_*`, which are not phases of it) add up to its wall time, so a share is
taken over their sum: the untraced seconds as the thread's own clock has
them, whatever the scrapes took.  A program without the table (an older
commit) gives nothing to read.

what="busy_pct": the rows' time as a share of the thread's wall time.
what="per_count_us": the rows' time over the count of the row `per`, in
microseconds (`turn` over `parse`: what one request costs the thread).
what="ratio": the count of the row `per` over the count of the row `den`.
what="mean_ms": the sum of each row's own mean (its time over its count), in
milliseconds (the three `req_*` rows: a request's residence in the server).
"""


def _front(dv):
    return (dv.get("native_frontend") or {}).get("front") or {}


def read(ctx, what, rows=(), per=None, den=None):
    front0, front1 = _front(ctx["vars0"]), _front(ctx["vars1"])
    phases = front1.get("phases")
    if not phases or not front0.get("phases"):
        return None
    before = {**front0["phases"], **(front0.get("rows") or {})}
    after = {**phases, **(front1.get("rows") or {})}
    if any(r not in after for r in (*rows, per, den) if r):
        return None

    def delta(row, field):
        return after[row][field] - (before.get(row) or {}).get(field, 0)

    total_ns = sum(delta(r, "sum_ns") for r in rows)
    if what == "busy_pct":
        wall_ns = sum(delta(r, "sum_ns") for r in phases)
        return 100.0 * total_ns / wall_ns if wall_ns > 0 else None
    if what == "per_count_us":
        n = delta(per, "count")
        return total_ns * 1e-3 / n if n > 0 else None
    if what == "ratio":
        below = delta(den, "count")
        return delta(per, "count") / below if below > 0 else None
    if what != "mean_ms":
        raise ValueError(what)
    counts = [delta(r, "count") for r in rows]
    if not counts or min(counts) <= 0:
        return None
    return sum(delta(r, "sum_ns") * 1e-6 / n for r, n in zip(rows, counts))
