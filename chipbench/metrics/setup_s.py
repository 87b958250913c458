"""Seconds from the process's start to the first measured step: imports,
building and compiling the step (or loading it from the persistent
cache), making weights and inputs, and the three steps the comparison
reads (host clock)."""


def read(rec):
    return rec.setup_s
