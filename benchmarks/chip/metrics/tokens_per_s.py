"""Output tokens of the steps whose Result falls in the window (one per
lane decoded, one per request prefilled), over the window's seconds."""
from chipbench import measures


def read(run):
    return measures.tokens_in_window(run) / run.seconds
