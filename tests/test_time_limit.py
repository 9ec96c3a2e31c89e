"""The per-test time limit (conftest.py): a test that runs past it fails on
its own with a message naming the limit; a test that finishes in time is
untouched."""
from pathlib import Path

pytest_plugins = ("pytester",)


def test_time_limit_fails_only_the_test_that_overruns(pytester):
    # The inner run gets this suite's conftest, with the limit patched down
    # to 1 s.
    conftest = (Path(__file__).parent / "conftest.py").read_text()
    pytester.makeconftest(conftest + "\nTEST_TIME_LIMIT_S = 1\n")
    pytester.makepyfile(test_inner="""
        import time


        def test_sleeps_past_the_limit():
            time.sleep(30)


        def test_finishes_in_time():
            time.sleep(0.1)
    """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines([
        "*test_sleeps_past_the_limit*",
        "*TimeLimitExceeded: test exceeded the per-test time limit of 1 s*",
    ])
    assert result.duration < 20
