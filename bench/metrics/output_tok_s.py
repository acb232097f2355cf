"""Tokens that reached clients inside the window, per second of it."""
from harness import reading


def read(run):
    return reading.tokens_in_window(run) / run.window.seconds
