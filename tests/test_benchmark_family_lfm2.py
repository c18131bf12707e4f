"""The benchmark's LFM2-MoE family, counted by tier-1: the cases of
``benchmark/tests/test_family_lfm2.py`` as they stand (the seeded weights,
the framing, the work and byte counts at the cut, the reference's control and
a tiny cell run end to end on the CPU rehearsal). The new cell's ``correct``
and its ``moe_embed_roofline`` rest on them, and ``python -m pytest
benchmark/tests`` is not among the tier-1 commands."""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests"))

import test_family_lfm2  # noqa: E402

globals().update(
    {name: case for name, case in vars(test_family_lfm2).items() if name.startswith("test_")})


def test_every_case_of_the_benchmarks_file_is_collected_here():
    assert sum(name.startswith("test_") for name in vars(test_family_lfm2)) >= 8
