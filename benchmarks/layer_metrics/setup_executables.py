"""Executables the program's set-up built or loaded: its
``compile.backend`` spans enclosed by a set-up span before the window
(``benchmarks/setup_reads.py``). The reference check's own executables
run between the set-up spans and are not counted."""

from benchmarks.setup_reads import executables


def read(view):
    return executables(view)
