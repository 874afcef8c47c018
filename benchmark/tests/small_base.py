"""``run.py`` with the cell's ``--near-base`` cut to a few ten thousand
rows, for rehearsals on the CPU (``--rehearse`` shrinks the traffic, not
``sidecar_args``, and no test may wait on a 30M-row base):

    python3 benchmark/tests/small_base.py <rows> <run.py's arguments>

``run.py`` itself is not edited: what ``find_cell`` returns is patched
in this process.  The ``__main__`` guard matters: ``run.compare`` starts
its reference workers with ``spawn``, which imports this file again.
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def main(argv: list[str]) -> int:
    import run

    rows, real = argv[0], run.find_cell

    def small(name: str) -> dict:
        cell = real(name)
        args = cell["config"]["sidecar_args"]
        at = args.index("--near-base") + 1
        args[at] = f"{rows}:{args[at].split(':')[1]}"
        return cell

    run.find_cell = small
    return run.main(argv[1:])


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
