"""Seconds per right-hand side: the window's span, from the first solve's
call to the last one's ``block_until_ready`` (the last solve finished, not
cut), over the solves that converged and verified (host clock)."""


def read(view):
    ok = sum(1 for r in view.window.records if r and r.get("ok"))
    return (view.window.t_end - view.window.t0) / ok if ok else None
