"""Host seconds per experiment outside the tick loop: building the
cluster, sorting the request list, and collecting the finished requests
into the result, the program's ``build``, ``intake`` and ``result``
spans."""

SPANS = ("build", "intake", "result")


def read(run):
    exps = [e for e in run["host_experiments"]
            if any(s in e["phases"] for s in SPANS)]
    if not exps:
        return None
    total = sum(e["phases"][s][0] for e in exps for s in SPANS
                if s in e["phases"])
    return total / len(exps)
