"""The benchmark's own tests (not part of tier-1, which runs tests/):
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -n 6

Every test that reads `BENCHMARK.json`'s lists takes the fixture `root`
and runs twice: on the repo, and on a copy that a later PR appended to
(`later_pr.py`). A test that pins a place in a list, `[-1]` or a count
from either end, fails on the copy in the run of the PR that writes it.
Find entries by name (`entries.named`).
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests import later_pr  # noqa: E402


@pytest.fixture(scope="session")
def appended_root(tmp_path_factory):
    root = later_pr.copy_of_the_benchmark(tmp_path_factory.mktemp("later_pr"))
    later_pr.append_to(root)
    return str(root)


@pytest.fixture
def root_of(request):
    """For a test parametrised over both roots' entries while it was
    collected: the root whose entry it was given."""
    def of(which):
        return ROOT if which == "repo" \
            else request.getfixturevalue("appended_root")
    return of


@pytest.fixture(params=later_pr.ROOTS)
def root(request, root_of):
    """The repo, then the copy a later PR appended to."""
    return root_of(request.param)
