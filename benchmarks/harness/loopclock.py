"""What the readers of the ``loop`` layer share: the seconds of the one
event loop that the program's daemons charged to themselves in the
window, by role.  Each daemon's messenger times every loop callback it
owns into ``loop_s_<role>`` (``ceph_tpu/common/tracing.py``); the
harness sums them over the OSDs, the mon and the client as
``msgr.loop_s_<role>``.
"""

ROLES = ("msgr", "op", "recovery", "background", "client")


def owned_s(counters: dict) -> dict | None:
    """``{role: seconds}``, or None where the program timed no callback
    (its tracers off, or a program that keeps no loop clock)."""
    if not counters.get("msgr.loop_callbacks"):
        return None
    return {r: counters.get(f"msgr.loop_s_{r}", 0.0) for r in ROLES}
