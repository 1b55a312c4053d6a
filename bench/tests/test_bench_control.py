"""The control and the planted faults, at a size a test run holds: the
plain reference computed in bfloat16 and put in the program's place
must come out not correct, as must each fault planted in the float32
reference put there; the program itself must come out correct."""
import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

from benchkit import check, control, spec  # noqa: E402


def _readings():
    s = importlib.util.spec_from_file_location(
        "bench_readings", BENCH / "readings.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", params=["lv2-tiny.xla", "lv2-tiny.kernel"])
def out(request):
    cell = spec.resolve(request.param, spec.load_spec(DATA / "BENCHMARK.json"),
                        DATA)
    return cell, _readings().readings(cell, 2 ** 31 + 99, 1, 1)


def test_program_is_correct(out):
    cell, r = out
    assert check.judge(r["program"][0], cell.limits), r["program"][0]


def test_control_is_not_correct(out):
    cell, r = out
    nums = r["control"][0]
    assert not check.judge(nums, cell.limits), nums


@pytest.mark.parametrize("fault", control.FAULTS)
def test_fault_in_reference_is_not_correct(out, fault):
    cell, r = out
    assert not check.judge(r[fault][0], cell.limits), r[fault][0]
