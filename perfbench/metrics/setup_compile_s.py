"""Seconds of set-up spent tracing, lowering, compiling and loading
programs from the persistent cache (JAX's monitoring events during
set-up; the backend-compile span holds the cache read on a hit)."""


def read(run):
    return run["compiles"]["seconds"]["setup"]
