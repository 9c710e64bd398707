"""Engine dispatch: the share of the decoding slots' table rows that a
dispatch built again from the page ledger: 100 x delta ``table_rows_rebuilt``
/ delta ``table_rows_checked`` (``ServingMetrics`` counters). A row is built
again only where the ledger's stamp of the sequence's pages moved since the
row was mirrored (a page taken, a rewind, a fresh seat); every other row costs
one compare. 0.0 where the window checked no row. Left out ONLY where the
program has no such counter (it rebuilt every row of every dispatch)."""


def read(run):
    c = run["counters_window"]
    rebuilt = c.get("table_rows_rebuilt")
    if rebuilt is None:
        return None
    checked = c.get("table_rows_checked", 0)
    return 100.0 * rebuilt / checked if checked else 0.0
