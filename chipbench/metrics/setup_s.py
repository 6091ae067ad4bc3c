"""Process start to the first timed call: JAX init, data, compile or cache
load, and warm-up (host clock)."""


def read(view):
    return view.setup_s
