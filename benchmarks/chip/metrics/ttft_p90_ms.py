"""90th percentile of the time to first token over every request due in
the window: due time to the Result that admitted it (prefill gives the
first token). A request never admitted counts up to the agent's stop."""
from chipbench import measures


def read(run):
    v = measures.percentile(measures.ttft_s(run), 90)
    return None if v is None else v * 1e3
