"""90th percentile (nearest rank), over every request due in the window,
of the time from the request's due time to its result (host clock).  A
request that failed or never came counts as infinitely late."""

import math


def read(view):
    lat = sorted(r["done"] - r["due"] if r.get("done") and r["ok"]
                 else math.inf for r in view.window.records)
    if not lat:
        return None
    p90 = lat[max(0, math.ceil(0.9 * len(lat)) - 1)]
    return p90 if math.isfinite(p90) else None
