"""Share of the traced window, in %, in which a collective (all-reduce, all-gather, ...) ran with no other operation on its chip, averaged over the chips."""


def read(r):
    return r.collective_exposed_pct()
