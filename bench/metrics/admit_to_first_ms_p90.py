"""90th percentile of admission to the drain that delivered the first token (the program's request times), over the requests due in the window."""

from bench import spans


def read(r):
    return spans.admit_to_first_ms_p90(r)
