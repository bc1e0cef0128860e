"""As paged_attention_roofline, read in a cell judged on time to first
token, where a request due during a decode step waits for it."""
from pathlib import Path

from chipbench import spec

read = spec.load_module(
    Path(__file__).with_name("paged_attention_roofline.py")).read
