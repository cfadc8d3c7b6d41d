"""The program's own batch stage clock: the cumulative table
/debug/vars native_frontend.stages, {stage: {count, sum_ns, max_ns}}, taken
as the difference between the two scrapes of the untraced part of the window
(vars0, vars1): served seconds, no profiler.  A program without the table
(an older commit) gives nothing to read.

what="mean_ms": the stages' time a batch, in milliseconds: the sum of their
sums over the count of the first of them.
what="busy_pct": the share of the untraced seconds that the threads which
run the stages spent in them: the sum of their sums over seconds times
threads, where `threads` names a number under native_frontend (one thread
where it names none).
"""


def _table(dv):
    return (dv.get("native_frontend") or {}).get("stages")


def read(ctx, stages, what, threads=None):
    before, after = _table(ctx["vars0"]), _table(ctx["vars1"])
    if not before or not after or any(s not in after for s in stages):
        return None

    def delta(stage, field):
        return after[stage][field] - (before.get(stage) or {}).get(field, 0)

    total_ns = sum(delta(s, "sum_ns") for s in stages)
    if what == "mean_ms":
        batches = delta(stages[0], "count")
        return total_ns * 1e-6 / batches if batches > 0 else None
    if what != "busy_pct":
        raise ValueError(what)
    seconds = ctx["untraced_s"]
    workers = ctx["vars1"]["native_frontend"].get(threads) if threads else 1
    if seconds <= 0 or not workers:
        return None
    return 100.0 * total_ns * 1e-9 / (seconds * workers)
