"""Seconds the program spent tracing its programs to jaxprs before the window
opened: the union of its ``build.trace`` spans (program_spans.py)."""

import program_spans


def read(r):
    return program_spans.setup_build_s("build.trace", r.trace.window_s)
