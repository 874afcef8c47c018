"""The benchmark's span reducer and its per-layer readers run with tier-1.
The cases live in ``benchmark/tests/test_host_spans.py``; nothing is copied."""

from benchmark.tests.test_host_spans import *  # noqa: F401,F403
