"""Tokens the decode chunks of the window's boundaries produced, over what the slots could have produced (boundaries x sync interval x slots), in %."""


def read(r):
    return r.decode_occupancy_pct()
