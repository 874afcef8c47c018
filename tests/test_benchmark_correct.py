"""The benchmark's CPU rehearsals run with tier-1: a sound run compares
clean, the ``failopen`` control and the planted faults come out not correct,
``restic_chunks.snapshots`` is rehearsed at its real chunk widths with
its two readers, ``source_dedup.negotiated`` through the negotiated
upload with its six, and ``crawl_neardup.revisit`` over a small base with
its seven.  The cases live in ``benchmark/tests/test_correct.py``,
``test_widths.py``, ``test_negotiated.py`` and ``test_neardup.py``; nothing
is copied.  One module for the four files: every rehearsal works in the one ``benchmark/_run`` directory, so
they must run one after another, and the driver's workers take whole files
(``--dist loadfile``)."""

from benchmark.tests.test_correct import *  # noqa: F401,F403
from benchmark.tests.test_widths import *  # noqa: F401,F403
from benchmark.tests.test_negotiated import *  # noqa: F401,F403
from benchmark.tests.test_neardup import *  # noqa: F401,F403
