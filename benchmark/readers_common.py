"""What more than one reader takes from the run's context."""


def ledger_delta(ctx, lane, field, first="vars0", last="vars1"):
    """The kernel-cost ledger's count between two readings of /debug/vars:
    the window's open and the run's end, or those the context names."""
    def at(dv):
        fe = dv.get("native_frontend") or {}
        ledger = (fe.get("kernel_cost") or {}).get("ledger") or {}
        return (ledger.get(lane) or {}).get(field, 0)
    return at(ctx[last]) - at(ctx[first])
