"""The control of ``correct``: the reference in float8, put in the
program's place, reads well above the program and fails the cell's limit.

On the chip ``bench/calibrate.py`` reads both at the cell's own size over
a dozen seeds; this keeps the same comparison at test widths on the CPU,
through the same window and sample as a run.
"""
import pytest

from bench import calibrate, manifest, serve
from conftest import shrink

SERVING = [w["name"] for w in manifest.json.loads(
    manifest.MANIFEST.read_text())["workloads"]]


@pytest.mark.parametrize("workload", SERVING)
def test_control_fails_the_limit(workload):
    cell = shrink(manifest.load_cell(workload))
    # outputs long enough that the sample holds some hundred served tokens
    cell.mix.update(output={"median": 24, "sigma": 0.3, "min": 16, "max": 40},
                    engine={"max_batch": 4, "max_len": 96})
    limit = serve.check.load_limits(workload)["numbers"][
        "served_logit_gap"]["limit"]
    row = calibrate.one_seed(cell, 2**35 + 9, 3.0, serve.CompileCounter())
    assert row["sampled_tokens"] >= 100
    assert row["served_logit_gap"] <= limit
    assert row["control_served_logit_gap"] > limit
    assert row["control_served_logit_gap"] >= 3 * row["served_logit_gap"]
