"""Host scheduler: what the window layers hold of a full cache. The engine
samples, every decode dispatch, the pages its seated sequences hold in a full
layer (``kv_pages_full``) and what a window layer holds of them at most, its
ring (``kv_pages_window``): the ratio of the two sums over the window, in per
cent. 100 = the window never binds; lower = memory and walk a window layer is
spared. A program without rings samples neither and the metric is left out."""


def read(run):
    c = run["counters_window"]
    full, held = c.get("kv_pages_full.total"), c.get("kv_pages_window.total")
    return 100.0 * held / full if full and held is not None else None
