"""The benchmark's reader of the daemon's stage lines runs with tier-1.
The cases live in ``benchmark/tests/test_daemon_spans.py``; nothing is copied."""

from benchmark.tests.test_daemon_spans import *  # noqa: F401,F403
