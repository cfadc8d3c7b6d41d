"""A number the program reports about itself, at a path of the /debug/vars
reading taken just before the profiler starts (vars1).  A program that does
not report it gives nothing to read."""


def read(ctx, path):
    at = ctx.get("vars1")
    for key in path:
        if not isinstance(at, dict) or key not in at:
            return None
        at = at[key]
    return at if isinstance(at, (int, float)) and not isinstance(at, bool) else None
