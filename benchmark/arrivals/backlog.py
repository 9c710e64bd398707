"""Closed loop: a pool of requests with no due times. The load generator takes
them in order (cycling the pool) whenever fewer than ``queue_depth`` wait in
the system's queue, so every slot stays full from the ramp to the end.

    "arrival": {"process": "backlog", "queue_depth": 4, "pool_requests": 1024}
"""


def schedule(arrival: dict, order, ramp_s: float, seconds: float,
             after_s: float) -> list:
    """[(section, due times)]: one section, ``None`` for 'due when taken'."""
    return [("backlog", [None] * int(arrival["pool_requests"]))]
