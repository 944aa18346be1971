"""The benchmark's own modules import each other as `harness`,
`builders`, `drivers` (run.py puts `benchmarks/` on the path); its
tests do the same."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(ROOT, "benchmarks")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import perfbench_newarch  # noqa: E402
from perfbench_pending import manifest_with_pending  # noqa: E402


@pytest.fixture(scope="session")
def root_with_pending(tmp_path_factory):
    """A directory that stands for the checkout with the pending cells
    admitted: its own BENCHMARK.json, the rest linked."""
    root = str(tmp_path_factory.mktemp("admitted"))
    for d in ("benchmarks", "paddle_tpu"):
        os.symlink(os.path.join(ROOT, d), os.path.join(root, d))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest_with_pending(), f)
    return root


@pytest.fixture(scope="session",
                params=["as_committed", "one_more_architecture"])
def bench_root(request, tmp_path_factory):
    """The checkout a test of the manifest's shape reads: this one as
    committed, and a copy with one more architecture's configuration,
    decode cell and readers appended as a change that adds a model
    appends them (`perfbench_newarch`). A check that holds on the first
    and not on the second pins something a new cell has to move."""
    if request.param == "as_committed":
        return ROOT
    return perfbench_newarch.copy_with_a_new_architecture(
        str(tmp_path_factory.mktemp("one_more_architecture")))
