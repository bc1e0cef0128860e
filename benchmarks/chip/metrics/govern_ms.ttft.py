"""As govern_ms.itl, read in a cell judged on time to first token, where a
request due during a step waits for it."""
from pathlib import Path

from chipbench import spec

read = spec.load_module(Path(__file__).with_name("govern_ms.itl.py")).read
