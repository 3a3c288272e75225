"""Most layer-0 KV pages in use at once over the run (the page pool's high-water mark), of the pool's pages, in %."""


def read(r):
    return r.pages_peak_pct()
