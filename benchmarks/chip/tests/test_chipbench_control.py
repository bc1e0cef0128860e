"""The control: the reference put in the program's place in bfloat16,
the precision below the configuration's float32, comes out not correct
where the float32 engine is correct. ``control.py`` reads it on the chip
at each cell's own size; here it runs at a size a test run holds, with
limits set at that size the way the cells' are: the float32 engine reads
0 on the CPU, and the control read at least 0.0052 (mean gap), 0.1096
(widest gap) and 11.46% (tokens flipped) over three seeds.
"""
import pytest

import chipbench_tiny as tiny

# deep and wide enough a model and vocabulary that bfloat16 reorders
# near-ties in every sample
CONTROL_CONFIG = dict(tiny.TINY_CONFIG, hidden_size=256, head_dim=64,
                      intermediate_size=512, vocab_size=32768,
                      num_hidden_layers=4)
TINY_LIMITS = {"mean_logit_gap": 0.001, "widest_logit_gap": 0.03,
               "flipped_pct": 2.0}


@pytest.mark.parametrize("gap", sorted(TINY_LIMITS))
def test_bfloat16_control_is_not_correct(tmp_path, gap):
    limits = {gap: {"limit": TINY_LIMITS[gap]},
              "compared_tokens": {"at_least": 16}}
    root = tiny.make_root(tmp_path, config=CONTROL_CONFIG, limits=limits)
    line = tiny.run(root, seconds=3.0, control=True)
    assert line["correct"], line["checks"]
    assert line["control_correct"] is False
    assert line["control"][gap] > TINY_LIMITS[gap]
