"""Model programs: the mean context a live row walks in ONE cache plane of a
LOOPED decoder, over the window: the program's ``loop_plane_keys`` (keys
walked, summed over live rows, the walks x layers planes and inner steps) over
``loop_row_calls`` (live rows summed the same way: 192 a live row and
token-step). It is the length of one of the 192 walks a token-step makes; the
walks' bytes are this x 8,192 B x ``loop_row_calls``. A program without the
counters leaves the metric out."""


def read(run):
    c = run["counters_window"]
    keys, calls = c.get("loop_plane_keys"), c.get("loop_row_calls")
    if keys is None or not calls:
        return None
    return keys / calls
