"""Whole model step: the model operations of the tokens prefilled and
decoded in the traced window (chipbench/flops.py: every layer's
matmuls, causal attention over the real keys, the head once per
generated token) over the window's seconds times the chip's bf16 peak,
in percent."""
from chipbench import flops, measures


def read(run):
    if run.trace is None or run.peaks is None or not run.trace["device"]:
        return None
    work = sum(flops.decode_flops(run.cfg, r["attn_lens"])
               for r, _, _ in measures.traced(run, "decode"))
    work += sum(flops.prefill_flops(run.cfg, r["n_tokens"])
                for r, _, _ in measures.traced(run, "prefill"))
    if work == 0:
        return None
    return work / (run.trace["window_s"]
                   * run.peaks["bf16_flops_per_s"]) * 100
