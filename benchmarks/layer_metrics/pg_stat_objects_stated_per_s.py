"""Objects the OSDs stat'ed a second to report their PGs' objects and
bytes (the beacon's pg stats and the mgr report): the growth of
``osd.pg_stat_objects_stated``, summed over the daemons, over the
window's seconds.  Where the report keeps tallies this follows the names
the window's transactions changed; a report that scans every object
reads the pools' whole population a second.  Nothing to read where the
program keeps no such counter.
"""

LAYER = "loop"
UNIT = "1/s"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    stated = counters.get("osd.pg_stat_objects_stated")
    return None if stated is None else stated / run["window"].seconds
