"""As itl_p95_ms, read in a cell judged on time to first token. There
the gaps' tail is the steps that carry long prefills, and their p95
falls between the levels of the prompt buckets, so it jumps from run to
run: a layer reading, not an end-to-end one."""
from pathlib import Path

from chipbench import spec

read = spec.load_module(Path(__file__).with_name("itl_p95_ms.py")).read
