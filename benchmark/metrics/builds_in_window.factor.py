"""Programs the program built inside the traced window, from its own build
counter: ``build.compile`` spans made while the profiler recorded, cache
loads and compiles alike (program_spans.py).  A driver builds everything in
set-up, so this reads 0."""

import program_spans


def read(r):
    return program_spans.builds_in_window()
