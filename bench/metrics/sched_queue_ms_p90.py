"""90th percentile of submission to admission in the scheduler (the program's request times), over the requests due in the window."""

from bench import spans


def read(r):
    return spans.sched_queue_ms_p90(r)
