"""Mean wait of a request admitted in the window, from when it could first
be scheduled to its admission, ms: the engine's ``queue_wait_s`` over its
``admissions``.  None where the program keeps no such counters."""


def read(rec):
    n = rec["stats"].get("admissions", 0)
    if not n:
        return None
    return rec["stats"]["queue_wait_s"] / n * 1e3
