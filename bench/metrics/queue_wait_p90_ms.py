"""90th percentile of due time to the boundary that admitted the request, over the requests due in the window."""


def read(r):
    return r.queue_wait_p90_ms()
