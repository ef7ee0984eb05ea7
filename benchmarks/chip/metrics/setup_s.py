"""Seconds from the process's start to the end of the warm-up phases:
imports, weights, pretraining, compiles or cache loads, warm-up."""


def read(ctx):
    return ctx["setup_s"]
