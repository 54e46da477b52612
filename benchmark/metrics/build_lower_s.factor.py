"""Seconds the program spent lowering jaxprs to MLIR modules before the window
opened: the union of its ``build.lower`` spans (program_spans.py)."""

import program_spans


def read(r):
    return program_spans.setup_build_s("build.lower", r.trace.window_s)
