#!/usr/bin/env python3
"""One traced run of a cell of ``BENCHMARK.json`` in THIS process (the
harness's second child, run directly: no supervisor, so a cold compile
cache is filled here and the window runs after it), which also writes
what the ``lfm2_*`` readers were handed: the traced steps, each device
instruction's seconds on the first device and its ``op_name`` in the
compiled step (the program's scope table), for every instruction under a
``shortconv`` scope and the ``--others`` slowest of the rest.

    python3 tests/benchmarks/data/lfm2_controls/record_view.py \\
        lfm2_view.json --workload lfm2_ep4_train_8k \\
        --seed 2000000011 --seconds 20 --trace 1      # on the chip

``tests/benchmarks/data/lfm2_trace_view.json`` was cut so, on a TPU v5e:
the readers' recorded fixture. The run's own lines are printed as
``benchmarks/run.py`` prints them."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", "..", "..", ".."))
sys.path.insert(0, ROOT)


def main():
    out, argv = sys.argv[1], sys.argv[2:]
    others = 40
    if "--others" in argv:
        at = argv.index("--others")
        others = int(argv[at + 1])
        del argv[at:at + 2]
    from benchmarks import joyai_reads, lfm2_reads, run
    from cxxnet_tpu.telemetry.traceparse import scope_path
    inner = lfm2_reads.kind_seconds

    def kind_seconds(view):
        got = joyai_reads._program()
        if view.get("trace") is not None and got is not None:
            table, dev = got[0], view["trace"]["devices"][0]
            name = lambda key: key.rsplit(" ", 1)[-1].lstrip("%")
            mine = {k: s for k, s in dev["by_name"].items()
                    if set(lfm2_reads.SCOPES) & set(scope_path(
                        table.get(name(k)) or "")[1])}
            rest = sorted((kv for kv in dev["by_name"].items()
                           if kv[0] not in mine), key=lambda kv: -kv[1])
            keep = dict(mine, **dict(rest[:others]))
            with open(out, "w") as f:
                json.dump({"steps": dev["steps"], "by_name": keep,
                           "scope_table": {name(k): table.get(name(k))
                                           for k in keep}}, f, indent=0)
        return inner(view)
    lfm2_reads.kind_seconds = kind_seconds
    return run.main(argv + ["--attempt", "2",
                            "--started-at", repr(time.perf_counter())])


if __name__ == "__main__":
    sys.exit(main())
