"""Seconds of compiling or fetching compiled programs from the persistent
cache inside the window, per iteration (JAX monitoring events)."""


def read(run):
    if not run.iterations:
        return None
    return run.compile_in_window["compile_s"] / len(run.iterations)
