"""The chip benchmark's control on the CPU at tiny sizes: the plain
reference in float8, put in the program's place, fails the cell's
limits; the program, against the same float32 reference, passes.  On the chip the same
readings, at the cells' own sizes, set the limits (bench/calibrate.py)."""
import json

import pytest

import tiny_cells


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_cells.make_checkout(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,seconds", [("serve.tiny", 2)])
def test_control_is_not_correct(checkout, workload, seconds):
    """The plain reference in float8 in the program's place fails the
    cell's limits; the program, against the same reference, passes."""
    p = tiny_cells.python(
        checkout, "import calibrate; sys.exit(calibrate.main(" + repr(
            ["--workload", workload, "--seeds", "21,22", "--seconds",
             str(seconds)]) + ", require_chip=False))", timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    limits = json.loads((checkout / "bench" / "cells" /
                         f"{workload}.json").read_text())["limits"]
    rows = [json.loads(l) for l in p.stdout.splitlines()
            if l.startswith("{")]
    assert len(rows) == 2
    for row in rows:
        r = row["readings"]
        assert all(r["program"][k] <= limits[k] for k in r["program"])
        assert any(v > limits[k] for k, v in r["control"].items()), r
