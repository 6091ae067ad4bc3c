"""Mean ``RequestStats.queue_s`` (submit to batch dispatch) of the
window's served requests, in ms."""


def read(view):
    q = [r["queue_s"] for r in view.window.records
         if r and r.get("ok") and "queue_s" in r]
    return 1000.0 * sum(q) / len(q) if q else None
