"""A quantile of one on-box stage of the native frontend, in microseconds,
from the bucket deltas of auth_server_frontend_stage_duration_seconds over
the window.  The histogram's _sum is estimated by the program from bucket
midpoints, so no mean is taken from it; within a bucket the quantile is
interpolated linearly."""

NAME = "auth_server_frontend_stage_duration_seconds_bucket"


def _buckets(metrics, stage):
    out = {}
    for labels, value in metrics.get(NAME, ()):
        if labels.get("stage") == stage:
            out[float(labels["le"].replace("+Inf", "inf"))] = value
    return out


def read(ctx, stage, q):
    before, after = _buckets(ctx["metrics0"], stage), _buckets(ctx["metrics1"], stage)
    edges = sorted(after)
    cum = [after[le] - before.get(le, 0.0) for le in edges]
    if not cum or cum[-1] <= 0:
        return None
    want = q * cum[-1]
    lo_edge, lo_cum = 0.0, 0.0
    for le, c in zip(edges, cum):
        if c >= want:
            if le == float("inf"):
                return lo_edge * 1e6
            share = (want - lo_cum) / (c - lo_cum) if c > lo_cum else 1.0
            return (lo_edge + share * (le - lo_edge)) * 1e6
        lo_edge, lo_cum = le, c
    return None
