"""Host time of one per-tick group step spent waiting for the device, in
milliseconds: the program's ``jax_sync`` span (inside ``jax_step``, from
the jitted tick's return until its scalars and mirrors are on the host)
over the number of such waits."""


def read(run):
    total = calls = 0
    for e in run["host_experiments"]:
        if "jax_sync" in e["phases"]:
            total += e["phases"]["jax_sync"][0]
            calls += e["phases"]["jax_sync"][1]
    return total / calls * 1e3 if calls else None
