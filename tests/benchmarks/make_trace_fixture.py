#!/usr/bin/env python3
"""Look at a profiler dump by hand, and cut it down to a few steps,
small enough to check in as a test's fixture.

    python tests/benchmarks/make_trace_fixture.py list <in.xplane.pb[.gz]>
    python tests/benchmarks/make_trace_fixture.py cut <in> <out.xplane.pb.gz> [--steps 2] [--skip 2]

A tool for whoever records the next reference trace, not part of a run
or of a test. A traced run leaves its dump and the step's
``mxu_calls.json`` under ``benchmarks/.cache/trace/<cell>/``. ``cut``
needs TensorFlow's ``xplane_pb2`` to write the protobuf, which the
harness and the tests do not (they read with
``jax.profiler.ProfileData``).

``cut`` keeps, of every ``/device:TPU:<n>`` plane, the lines
``XLA Modules``, ``XLA Ops`` and — collectives only — ``Async XLA Ops``,
from the start of train-step run ``--skip`` to the end of run
``--skip + steps + 1`` (the reduction leaves out the first run it sees,
so ``steps`` whole periods remain). Every stat is dropped, and an
instruction's operand list is cut out of its name (``fusion(...)``):
shapes, opcode, ``kind=``, ``calls=`` and ``custom_call_target=`` — all
the reduction reads — stay.
"""

import argparse
import gzip
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import trace_reduce  # noqa: E402

KEEP_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
_OPERANDS = re.compile(r"^(%?[\w.\-]+ = .*?\s[\w\-]+)\((.*)\)(.*)$", re.S)
_ATTRS = re.compile(r'(kind=\w+|calls=%[\w.\-]+|custom_call_target="[^"]+")')


def read_bytes(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def listing(blob: bytes, limit: int) -> str:
    """Planes, lines, event counts, and the first events of each device
    line with their stats: what ``trace_reduce.py`` was written
    against."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_serialized_xspace(blob).planes:
        out.append(f"PLANE {plane.name} {dict(plane.stats)}"[:400])
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
                continue
            for e in events[:limit]:
                stats = {k: str(v)[:48] for k, v in e.stats}
                out.append(f"    {e.name[:100]!r} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f} {stats}")
    return "\n".join(out)


def shorten(name: str) -> str:
    m = _OPERANDS.match(name)
    if not m:
        return name
    attrs = ", ".join(_ATTRS.findall(m.group(3)))
    return f"{m.group(1)}(...)" + (", " + attrs if attrs else "")


def cut(space, steps: int, skip: int):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    out = xplane_pb2.XSpace()
    lo = hi = None
    for plane in space.planes:          # the range, from the first chip
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            by = {}
            for e in line.events:
                by.setdefault(e.metadata_id, []).append(e)
            runs = sorted(max(by.values(), key=lambda v: sum(
                e.duration_ps for e in v)), key=lambda e: e.offset_ps)
            first, last = runs[skip], runs[skip + steps + 1]
            base = line.timestamp_ns * 1000
            lo = base + first.offset_ps
            hi = base + last.offset_ps + last.duration_ps
        break
    if lo is None:
        raise SystemExit("no /device:TPU plane with an XLA Modules line")
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if line.name not in KEEP_LINES:
                continue
            base = line.timestamp_ns * 1000
            kept = []
            for e in line.events:
                start = base + e.offset_ps
                if start < lo or start + e.duration_ps > hi:
                    continue
                name = plane.event_metadata[e.metadata_id].name
                if line.name == "Async XLA Ops" and not \
                        trace_reduce.category(name)[1].startswith(
                            trace_reduce.COLLECTIVES):
                    continue
                kept.append(e)
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e in kept:
                nl.events.add(metadata_id=e.metadata_id,
                              offset_ps=e.offset_ps,
                              duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        for mid in sorted(used):
            meta = plane.event_metadata[mid]
            new.event_metadata[mid].id = mid
            new.event_metadata[mid].name = shorten(meta.name)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    ls = sub.add_parser("list")
    ls.add_argument("src")
    ls.add_argument("--limit", type=int, default=12)
    ct = sub.add_parser("cut")
    ct.add_argument("src")
    ct.add_argument("dst")
    ct.add_argument("--steps", type=int, default=2)
    ct.add_argument("--skip", type=int, default=2)
    args = ap.parse_args(argv)
    blob = read_bytes(args.src)
    if args.what == "list":
        print(listing(blob, args.limit))
        return 0
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(blob)
    blob = cut(space, args.steps, args.skip).SerializeToString()
    with gzip.GzipFile(args.dst, "wb", mtime=0) as f:
        f.write(blob)
    print(f"{args.dst}: {len(blob)} bytes before gzip", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
