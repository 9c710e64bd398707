"""Kernels / HBM: device time of a prefill chunk's K/V WRITE per execution of
the chunk program: the operations that start inside the chunk program (device
0) and yield a pool's row-major 2-D view ``[(L x) P x Hkv x page_size, D]``,
the array every write of new keys and values goes through
(``ops/flash_decode.py::paged_kv_write``). A row scatter is one such fusion a
pool leaf and layer; a write that goes page by page is a ``while`` that
carries the views (its body's page read, select and ``dynamic-update-slice``
lie inside it): both are found by the shape of what they yield, so the metric
reads on a program with either. A view is told from any other array by what
it is: two-dimensional, a whole number of pages long, and longer (2**19 rows)
than any table, embedding or block of activations a one-chip program holds.
Nested matches count once (the union of their intervals). The page-aligned
copy of the new rows that the page-wise write makes BEFORE its visits yields
no view and is left out (under 3 us a layer).

Counted by CALLS x calls a chunk (the most any one execution shows: a whole
one), not by the chunk program's events: the profiler's stop cuts the last
execution short, and an event part of whose calls are missing reads low. Left
out by a program whose chunk writes no such view."""
import re

from benchmark import trace as T
from benchmark.layer_metrics.chunk_ms import PATTERN as CHUNK

MIN_ROWS = 1 << 19
ARRAY_2D = re.compile(r"\b[a-z]+\d+\w*\[(\d+),(\d+)\]")
OPCODE = re.compile(r"\) [a-z][a-z0-9\-]*\(")


def yields_a_view(name: str, page_size: int) -> bool:
    """Whether the HLO line ``name`` yields a pool's 2-D view, alone or as an
    element of a tuple."""
    _, sep, rest = name.partition(" = ")
    if not sep:
        return False
    if rest.startswith("("):
        m = OPCODE.search(rest)
        result = rest[:m.start() + 1] if m else ""
    else:
        result = rest.split(" ", 1)[0]
    return any(int(rows) >= MIN_ROWS and int(rows) % page_size == 0
               for rows, _ in ARRAY_2D.findall(result))


def read(run):
    tr = run["trace"]
    if tr is None or not tr.ops or not tr.modules:
        return None
    page_size = run["cfg"].get("engine", {}).get("page_size")
    if not page_size:
        return None
    dev = min(tr.ops)
    writes = [e for e in tr.ops[dev] if yields_a_view(e[0], page_size)]
    secs, calls, most = 0.0, 0, 0
    for _, lo, d in T.matching(tr.modules[dev], CHUNK):
        inside = [e for e in writes if lo <= e[1] < lo + d]
        n, end = 0, lo
        for _, s, dur in inside:                # sorted by start
            n += s >= end                       # not nested in the last call
            end = max(end, s + dur)
        secs += T.union_s(inside)
        calls += n
        most = max(most, n)
    return secs * 1e3 * most / calls if calls else None
