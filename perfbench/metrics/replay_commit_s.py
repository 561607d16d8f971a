"""Host seconds per experiment spent pulling completion events, replaying
them, committing scan chunks and writing finished rows back: the
program's ``jax_events``, ``jax_commit`` and ``jax_writeback`` spans."""

SPANS = ("jax_events", "jax_commit", "jax_writeback")


def read(run):
    exps = [e for e in run["host_experiments"]
            if any(s in e["phases"] for s in SPANS)]
    if not exps:
        return None
    total = sum(e["phases"][s][0] for e in exps for s in SPANS
                if s in e["phases"])
    return total / len(exps)
