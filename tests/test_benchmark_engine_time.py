"""The benchmark's split of the device's idle time, counted by tier-1: the
cases of ``benchmark/tests/test_engine_time.py`` as they stand (the partition
of the traced window on hand-made spans, each of the nine readers on them and
on a span file of a program that does not cover its engine thread, a traced
CPU rehearsal that prints all nine). The readers rest on what the program
records (``tests/test_engine_spans.py``), and ``python -m pytest
benchmark/tests`` is not part of the driver's command."""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests"))

import test_engine_time  # noqa: E402

globals().update(
    {name: case for name, case in vars(test_engine_time).items() if name.startswith("test_")})


def test_every_case_of_the_benchmarks_file_is_collected_here():
    assert sum(name.startswith("test_") for name in vars(test_engine_time)) >= 5
