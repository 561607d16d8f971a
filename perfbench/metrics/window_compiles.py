"""Programs lowered, then compiled or loaded from the persistent cache,
while the window's experiments ran (JAX's lowering events): set-up
leaking into the window.  Should read 0."""


def read(run):
    return run["compiles"]["count"]["window"]
