"""95th percentile of the scheduler's boundary plan (host wall time), over the window's boundaries."""


def read(r):
    return r.plan_ms_p95()
