"""Output tokens emitted in the window over the window's seconds."""
from chipbench import window as W


def read(run):
    return W.tok_s(run.window)
