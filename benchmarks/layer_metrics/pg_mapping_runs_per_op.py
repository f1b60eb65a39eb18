"""Placement pipeline runs the OSDs made an acknowledged op: the growth
of ``osd.pg_mapping_runs`` (the OSDMap's mapping memo's misses), summed
over the daemons, over the ops acknowledged in the window.  A memo keyed
by the PG runs each PG once an epoch; one keyed by the object's raw pg
runs once per new name.  Nothing to read where the program keeps no such
counter.
"""

LAYER = "loop"
UNIT = "1/op"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    runs = counters.get("osd.pg_mapping_runs")
    if runs is None or not run.get("acked_ops"):
        return None
    return runs / run["acked_ops"]
