"""95th percentile, over the boundaries whose scheduler plan started in the window, of the end of the plan to the end of the boundary's drain (the engine's own spans): dispatch plus the device work the drain waits for."""

from bench import spans


def read(r):
    return spans.boundary_ms_p95(r)
