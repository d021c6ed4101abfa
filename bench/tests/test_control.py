"""The control comes out as not correct: the plain reference in the
program's place, one bfloat16 pass, at the same draws, reads above each
cell's limits of the log density and of the gradient, while the
program's own readings stay under them.

CPU, at small sizes: ``tiny.shrink`` with ``logreg`` kept at its 100
dimensions, since one bfloat16 pass loses more the more products a logit
sums. HIGH is left out: on a CPU it is float32, so only the chip reads
it."""
import io
import json
from contextlib import redirect_stdout

import pytest

import control
from harness import core
from tiny import shrink as tiny


def shrink(cell):
    tiny(cell)
    if cell.config["model"] == "logreg":
        cell.config["sizes"].update(n=4096, dim=100)

@pytest.fixture(autouse=True)
def fresh_programs(tmp_path, monkeypatch):
    from repro.core.program import clear_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    clear_cache()
    yield
    clear_cache()


@pytest.mark.parametrize("cell", ["logreg.fleet", "logreg.stan4"])
def test_bfloat16_control_fails_the_limit(cell):
    limits = core.load_json(core.BENCH / "limits" / f"{cell}.json")
    out = io.StringIO()
    with redirect_stdout(out):
        control.main(["--workload", cell, "--seeds", "4000000011,12",
                      "--calls", "3"], require_chip=False, shrink=shrink)
    rows = [json.loads(line) for line in out.getvalue().splitlines()]
    assert len(rows) == 2
    for row in rows:
        for name in ("logp_gap", "grad_gap"):
            assert row["program"][name] <= limits[name], row
            assert row["control"]["bfloat16"][name] > limits[name], row
