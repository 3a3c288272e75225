"""Share of the traced window in which no operation ran on the device."""


def read(r):
    return r.idle_pct()
