"""Seconds from process start to the opening of the measured window:
loading, weights, plan warmup, compilation, warm-up of every shape the
traffic uses, and the pre-roll that brings the traffic to a steady state."""


def read(run):
    return run.setup_s
