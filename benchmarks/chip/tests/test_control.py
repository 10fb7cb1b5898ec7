"""The control: the float32 reference with its projections rounded to
float8, put in the program's place over the prompts and tokens a run
served.  At a tiny size on the CPU it must read a wider gap than the
program does and than the tiny configuration's limit.  The readings at the
cell's own size on the chip, from which its limit was set, are in
PERF.md."""

from __future__ import annotations

import pytest

from chipfixtures import interpret, tiny_bench  # noqa: F401
import control


@pytest.mark.parametrize("seed", [777, 2**31 + 778])
def test_fp8_control_fails_where_program_passes(tiny_bench, interpret,
                                                seed):
    r = control.readings_for_seed(tiny_bench, tiny_bench.cell(
        "tiny-ssm-chat"), seed, 4.0, kernel_mode=interpret)
    assert r["tokens"] > 0
    assert r["program_gap"] <= r["limit"] < r["control_gap"], r
