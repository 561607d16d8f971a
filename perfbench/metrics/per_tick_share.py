"""Share of simulated ticks stepped one at a time, in percent: calls of
the program's per-tick ``step`` span over the ticks each experiment
simulated (its last finish tick).  The rest were carried by the scan
chunks and gap advances.  A count: it repeats exactly for a seed."""


def read(run):
    steps = ticks = 0
    for e in run["experiments"]:
        if "step" in e["phases"]:
            steps += e["phases"]["step"][1]
            ticks += e["ticks"]
    return steps / ticks * 100.0 if ticks else None
