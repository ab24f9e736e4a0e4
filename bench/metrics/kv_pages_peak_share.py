"""Peak share of the KV page pool in use during the window, from the
page allocator's high-water mark (reset when the window opens)."""


def read(run):
    if not run.pool_pages:
        return None
    return 100.0 * run.pool_high_water / run.pool_pages
