"""A ratio of kernel-cost ledger counters over the window: sums of
[lane, field] pairs from /debug/vars native_frontend.kernel_cost.ledger,
taken as the difference between the window's open and the run's end."""


from readers_common import ledger_delta


def _total(ctx, pairs):
    return sum(ledger_delta(ctx, lane, field) for lane, field in pairs)


def read(ctx, num, den, scale=1.0):
    below = _total(ctx, den)
    if below <= 0:
        return None
    return scale * _total(ctx, num) / below
