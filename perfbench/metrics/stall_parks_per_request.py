"""Parks per completed request: the program's ``stall_park`` counter
(one call per request parked on a stall event, summed from the device
step's outputs) over the requests the experiments completed.  A count:
it repeats exactly for a seed.  Under a mix where each request has at
most one stall that it always reaches, it reads the mix's stalled
share.  Nothing to read where no request stalls."""


def read(run):
    parks = done = 0
    seen = False
    for e in run["experiments"]:
        if "stall_park" in e["phases"]:
            seen = True
            parks += e["phases"]["stall_park"][1]
            done += e["completed"]
    return parks / done if seen and done else None
