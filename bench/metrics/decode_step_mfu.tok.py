"""Model flops of the tokens the decode chunks produced, over the decode programs' device time and the chips' peak."""


def read(r):
    return r.step_mfu_pct("decode")
