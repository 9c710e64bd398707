"""Kernels and HBM: the share of the pages a prefill chunk's K/V walks
(``gqa_prefill_paged``'s row blocks) that took the MASKED online-softmax
update in the window: 100 x delta ``chunk_walk_edge_pages`` / delta
``chunk_walk_pages`` (``ServingMetrics`` counters, counted on the host at
every chunk's commit from the plan the kernel walks by). An edge page is one
where some live row of the block does not see every key; every other page's
update runs without the mask. Left out where no chunk walked a page in the
window or the program has no such counter (a latent pool's chunk; a program
before the walk was a loop over live pages)."""


def read(run):
    c = run["counters_window"]
    pages, edge = c.get("chunk_walk_pages"), c.get("chunk_walk_edge_pages")
    return 100.0 * edge / pages if pages and edge is not None else None
