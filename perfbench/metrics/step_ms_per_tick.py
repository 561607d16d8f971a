"""Host time of one per-tick group step, in milliseconds: the program's
``jax_step`` span (the jitted tick dispatched and its outputs synced back
to the host) over the number of such steps."""


def read(run):
    total = calls = 0
    for e in run["host_experiments"]:
        if "jax_step" in e["phases"]:
            total += e["phases"]["jax_step"][0]
            calls += e["phases"]["jax_step"][1]
    return total / calls * 1e3 if calls else None
