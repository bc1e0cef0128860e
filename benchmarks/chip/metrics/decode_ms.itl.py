"""Engine decode step (PagedEngine.step: host batch preparation, the
device step and the wait for its tokens): mean wall time of the
benchmark's span around step, over the steps in the window: every
token waits for it."""
from chipbench import measures


def read(run):
    return measures.mean(measures.span_ms(run, "decode"))
