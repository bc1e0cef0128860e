"""95th percentile over every inter-token gap that ends in the window:
consecutive serve_step Results while a request holds a lane, each one
token of that request."""
from chipbench import measures


def read(run):
    v = measures.percentile(measures.itl_gaps_s(run), 95)
    return None if v is None else v * 1e3
