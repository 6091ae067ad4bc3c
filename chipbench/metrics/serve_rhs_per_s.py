"""Right-hand sides served, converged and verified by the window's close,
over the window (host clock).  Requests still out at the close count
neither as served nor as failed here."""


def read(view):
    w = view.window
    served = sum(1 for r in w.records
                 if r["ok"] and r.get("done") and r["done"] <= w.t_close)
    return served / (w.t_close - w.t0)
