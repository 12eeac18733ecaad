"""Paged cache (core/paged_cache.py): tokens that live sequences held in
the page pool, as a share of the pool's token slots (``num_pages`` x
``page_size``), averaged over the window's fused steps
(``live_token_steps`` / ``ragged_steps``).  None where the program does
not count them."""


def read(run):
    a, b = run.stats_open["runner"], run.stats_close["runner"]
    if "live_token_steps" not in a["pages"]:
        return None
    steps = b["ragged_steps"] - a["ragged_steps"]
    if steps <= 0:
        return None
    held = b["pages"]["live_token_steps"] - a["pages"]["live_token_steps"]
    pool = b["pages"]["num_pages"] * b["pages"]["page_size"]
    return 100 * held / (steps * pool)
