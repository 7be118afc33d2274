"""The control: the plain reference put in the program's place, computed in
the precision step below the configuration's bfloat16 (the configuration
file's ``control``), read by the same comparison on the same prompts and
tokens. At this test's tiny size it must read at least three times what the
program reads on one of the cell's numbers; on the chip, at the cell's own
size, its readings set each limit's upper end (PERF.md)."""
import pytest

import bench_chip_helpers as helpers

SEEDS = [5, 2**35 + 1]


def _control(config_name):
    return helpers.config_file(config_name)["control"]


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails_where_the_program_passes(seed):
    q = _control("qwen3-0.6b")
    res = helpers.run_tiny("tiny-qwen3", seed=seed, seconds=2.0, controls=(q,))
    prog = res["checks"]["prompt_logit_err"]["value"]
    ctrl = res["summary"][f"control_{q}.prompt_logit_err"]
    assert res["correct"], res["checks"]
    assert ctrl >= 3 * prog, (ctrl, prog)

