"""Mean CGNR iterations per right-hand side (``SolveStats.iterations``)."""


def read(view):
    its = [r["iterations"] for r in view.window.records
           if r and r.get("ok") and "iterations" in r]
    return sum(its) / len(its) if its else None
