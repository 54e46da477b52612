"""Seconds of backend builds before the window opened, persistent-cache loads
and compiles alike (each ``build.compile`` span says which in its ``cache``
tag): the union of the program's ``build.compile`` spans
(program_spans.py)."""

import program_spans


def read(r):
    return program_spans.setup_build_s("build.compile", r.trace.window_s)
