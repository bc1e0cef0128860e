"""Paged-attention kernel (kernels/paged_attention.py): the least time
the needed work could take on the chip over the kernel's device time in
the trace, in percent, over the decode steps of the traced window.

Needed work per call (one layer of one step): each decoded lane's pages
up to its attention length, K and V for every kv head, plus its query
and output (chipbench/flops.py). The least time is the larger of those
bytes over the HBM bandwidth and the operations over the bf16 peak. The
lengths come from the decode span, never from the block-table width, so
a kernel that stops walking empty pages shows as a gain.

The kernel is found by the name the trace gives its operation. The
paged kernel is the one Pallas call of the served decode step, and the
trace names it only as XLA lowered it, e.g. ``%closed_call.14 =
f32[16,8,4,128] custom-call(s32[16,160] ...)`` with
``custom_call_target="tpu_custom_call"``: a ``name=`` on its
``pallas_call`` would give it a name of its own.
"""
import re

from chipbench import flops, measures

KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def read(run):
    if run.trace is None or run.peaks is None or not run.trace["device"]:
        return None
    pairs = measures.traced(run, "decode")
    spent = sum(measures.device_time_in(run, KERNEL, pairs))
    if not pairs or spent <= 0:
        return None
    peak = run.peaks
    need = 0.0
    for rec, _, _ in pairs:
        c = flops.paged_attention_cost(run.cfg, rec["attn_lens"],
                                       run.traffic["page_size"])
        need += run.cfg["n_layers"] * max(
            c["bytes"] / peak["hbm_bytes_per_s"],
            c["flops"] / peak["bf16_flops_per_s"])
    return need / spent * 100
