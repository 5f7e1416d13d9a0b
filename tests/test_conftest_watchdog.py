"""`tests/conftest.py`'s rule at collection: no test that tier-1 runs carries
a watchdog longer than `LONGEST_TIER1_WATCHDOG_S`."""

import pathlib

import pytest

pytest_plugins = ["pytester"]

CONFTEST = pathlib.Path(__file__).with_name("conftest.py").read_text()

CASES = """
import pytest

@pytest.mark.timeout({seconds})
{slow}
def test_marked():
    pass
"""


def collect(pytester, seconds, slow):
    pytester.makeconftest(CONFTEST)
    pytester.makepyfile(CASES.format(
        seconds=seconds, slow="@pytest.mark.slow" if slow else ""))
    return pytester.runpytest_subprocess(
        "--collect-only", "-q", "-p", "no:cacheprovider")


@pytest.mark.parametrize("seconds, slow", [(600, False), (1800, True)])
def test_a_watchdog_inside_the_run_or_a_slow_one_is_collected(
        pytester, seconds, slow):
    result = collect(pytester, seconds, slow)
    assert result.ret == 0
    result.stdout.fnmatch_lines(["*::test_marked", "1 test collected*"])


def test_an_unmarked_watchdog_longer_than_the_run_is_refused_by_name(pytester):
    result = collect(pytester, 601, slow=False)
    assert result.ret == pytest.ExitCode.INTERRUPTED
    result.stdout.fnmatch_lines(
        ["*::test_marked: timeout(601) is above 600 s*", "*1 error*"])
