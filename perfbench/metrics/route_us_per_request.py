"""Host time of dispatch per routed request, in microseconds: the
program's ``route`` span (every arrival of a tick routed and delivered)
summed over the window's experiments, over the requests they routed."""


def read(run):
    total = n = 0
    for e in run["host_experiments"]:
        if "route" in e["phases"]:
            total += e["phases"]["route"][0]
            n += e["n"]
    return total / n * 1e6 if n else None
