"""JAX runtime: traces and compiles of the process inside the window
(``serve_obs.compiles()`` delta); a warmed-up run reads 0."""


def value(rec):
    return rec["counters"]["compiles"]
