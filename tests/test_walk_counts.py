"""The page walk's grid counters on a paged decode chunk's
``engine.generate`` span: ``walk_blocks`` (grid cells holding a visible
page) and ``walk_grid`` (all cells), against the count made from the
decoding requests' frontiers."""

import jax
import numpy as np

from repro.kernels.paged_attention import (WALK_BLOCK_TOKENS,
                                           walk_pages_per_block)
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve import scheduler as sm
from repro.serve import spans
from repro.serve.engine import Engine, EngineConfig

TINY = ModelConfig(
    name="tiny-walk", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=128,
)
PAGE_TOKENS, SLOTS, STEPS = 16, 3, 4
MAX_LEN = 2 * WALK_BLOCK_TOKENS + 4 * PAGE_TOKENS   # three blocks a slot


def test_walk_counts_follow_the_decoding_frontiers():
    model = build_model(TINY)
    engine = Engine(model, model.init(jax.random.PRNGKey(0)),
                    EngineConfig(max_len=MAX_LEN, eos_token=-1,
                                 sync_interval=STEPS))
    pb = sm.kv_bytes_per_token(TINY) * PAGE_TOKENS
    geom = sm.derive_page_geometry(TINY, MAX_LEN, page_tokens=PAGE_TOKENS,
                                   max_slots=SLOTS, layer0_bytes=pb * 4 * MAX_LEN // PAGE_TOKENS,
                                   layer1_bytes=pb * 8)
    p_max = geom.max_pages_per_slot
    ppb = walk_pages_per_block(PAGE_TOKENS, p_max)
    assert p_max > 2 * ppb, "the slot depth spans several blocks"
    sch = sm.Scheduler(n_slots=SLOTS, pages=geom)
    rng = np.random.RandomState(5)
    # frontiers either side of the first and second block edges; the
    # first chunk's last step ends on the edge, the second's crosses it
    bk = ppb * PAGE_TOKENS
    for prompt, new in ((bk - STEPS, 9), (bk - 2, 5), (2 * bk - 2, 6),
                        (30, 3), (2 * bk + 8, 7)):
        sch.submit(rng.randint(2, TINY.vocab_size, size=prompt), new)
    spans.clear()
    try:
        engine.serve(scheduler=sch)
        rec = spans.recent()
    finally:
        spans.clear()
    parent = {s.id: s for s in rec}
    grid = SLOTS * len({p // ppb for p in range(p_max)})
    chunks = [s for s in rec if s.name == "engine.generate"]
    assert chunks
    multi_block = False
    for gen in chunks:
        frontiers = [f for f, _ in parent[gen.parent].args["decode"]]
        last = [f + STEPS - 1 for f in frontiers]     # the last step's
        want = sum(len({p // ppb for p in range(p_max)
                        if p * PAGE_TOKENS <= f}) for f in last)
        assert gen.args["walk_grid"] == grid
        assert gen.args["walk_blocks"] == want <= grid
        multi_block |= want > len(frontiers)
    assert multi_block
