"""Host time of the stall bookkeeping per stepped tick, in milliseconds:
the program's ``stall_sync`` span (reading a step's or a scan chunk's
park and wake counts and updating the stalled-region mirrors that
dispatch reads) over the calls of the per-tick ``step`` span.  Nothing
to read where no request stalls."""


def read(run):
    total = steps = 0
    for e in run["host_experiments"]:
        ph = e["phases"]
        if "stall_sync" in ph and "step" in ph:
            total += ph["stall_sync"][0]
            steps += ph["step"][1]
    return total / steps * 1e3 if steps else None
