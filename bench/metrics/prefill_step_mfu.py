"""Model flops of the true prompt tokens admitted, over the admission programs' device time and the chip's peak."""


def read(r):
    return r.step_mfu_pct("admit")
