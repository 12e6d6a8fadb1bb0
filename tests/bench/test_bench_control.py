"""The lower-precision control comes out not correct through the harness's
own comparison: the reference computed with float8_e4m3fn where the
configuration states bfloat16, read at each position of the same prompts and
served tokens, reads a trimmed mean logit gap over the limit that the
program's own served tokens stay under."""

import numpy as np
import pytest

import bench_testlib as T


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    mix = T.tiny_mix("closed")
    mix["check"] = {"tokens": 200, "max_requests": 16}
    return T.make_root(tmp_path_factory.mktemp("control"),
                       cells=[("tiny.control", "tiny-phi", "tiny_control")],
                       configs={"tiny-phi": T.tiny_config("phi4-mini-3.8b", "tiny-phi")},
                       mixes={"tiny_control": mix})


@pytest.mark.parametrize("seed", [1, 3])
def test_control_fails_where_the_program_passes(root, seed):
    res = T.run_cell(root, "tiny.control", seed, 2.0, trace=False, control=True)
    assert res["correct"], res["compared"]
    ctl = res["control"]
    assert not ctl["correct"], ctl
    gap, ctl_gap = res["compared"]["logit_gap_trim90"], ctl["compared"]["logit_gap_trim90"]
    assert gap["value"] <= gap["limit"] < ctl_gap["value"]


def test_trimmed_gap_leaves_out_the_widest_tenth():
    from harness import check

    assert check.trim90(np.array([0.0] * 18 + [5.0, 7.0])) == 0.0
    assert check.trim90(np.full(20, 0.3)) == pytest.approx(0.3)
    assert check.trim90(np.arange(10.0)) == pytest.approx(4.0)  # 0..8
