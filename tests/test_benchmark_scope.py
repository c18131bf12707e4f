"""The benchmark's scope arithmetic, counted by tier-1: the cases of
``benchmark/tests/test_scope.py`` as they stand (the folders and bindings from
the seed, the bytes a request and a row carry, the reference's mask against the
grammar the program parses, how a scoped reply is judged). A scoped cell's
``correct`` rests on them, and ``python -m pytest benchmark/tests`` is not part
of the driver's command."""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests"))

import test_scope  # noqa: E402

globals().update(
    {name: case for name, case in vars(test_scope).items() if name.startswith("test_")})


def test_every_case_of_the_benchmarks_file_is_collected_here():
    assert sum(name.startswith("test_") for name in vars(test_scope)) >= 8
