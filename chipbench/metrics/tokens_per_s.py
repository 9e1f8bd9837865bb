"""Output tokens stamped in the window, over the window's seconds."""

from chipbench.record import tokens_in_window


def read(run):
    return tokens_in_window(run) / run.window_s
