"""Flash prefill attention (Pallas kernel) as a share of its roofline: causal flops and bytes at the true prompt lengths, over the kernel's device time."""


def read(r):
    return r.roofline_pct("flash")
