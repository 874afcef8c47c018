"""The benchmark's CPU rehearsals run with tier-1: a sound run compares
clean, the ``failopen`` control and the planted faults come out not correct.
The cases live in ``benchmark/tests/test_correct.py``; nothing is copied."""

from benchmark.tests.test_correct import *  # noqa: F401,F403
