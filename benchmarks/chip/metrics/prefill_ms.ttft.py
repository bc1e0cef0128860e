"""Engine prefill (PagedEngine.admit with KVPool.write_prefill): mean wall
time of the benchmark's span around admit, over the admissions in the
window."""
from chipbench import measures


def read(run):
    return measures.mean(measures.span_ms(run, "prefill"))
