"""Page-walk decode attention (Pallas kernel) as a share of its roofline: the bytes and flops the live slots' true lengths need, over the kernel's device time."""


def read(r):
    return r.roofline_pct("page_walk")
