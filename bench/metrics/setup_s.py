"""Seconds from the start of the process to the start of the window:
imports, device start, input, the traffic's set-up, the warm-up pass and
any compilation."""


def read(run):
    return run.setup_s
