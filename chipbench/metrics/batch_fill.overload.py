"""Real requests over padded batch slots, from ``server.metrics()``'s
batch and rung histograms counted over the window's requests, in %."""


def read(view):
    m = view.window.server_metrics
    if not m:
        return None
    real = sum(int(k) * v for k, v in m["batch_hist"].items())
    slots = sum(int(k) * v for k, v in m["rung_hist"].items())
    return 100.0 * real / slots if slots else None
