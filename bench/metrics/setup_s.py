"""Seconds from the start of the process to the first timed call: imports,
building the model, compiling or loading every program from the cache, and
one whole warm-up call of the cell's own shapes."""


def read(rec):
    return rec["setup_s"]
