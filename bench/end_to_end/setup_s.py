"""setup_s: process start to the end of the warm-up graph (host clock):
imports, plan, compile, and one whole graph."""


def read(run):
    return run.setup_s
