#!/usr/bin/env python
"""One elastic worker of a preemption-tolerant run (ROADMAP item 4).

Each invocation is ONE worker process with its own local device mesh;
workers coordinate through the shared ``elastic_dir`` (membership
heartbeats, generation agreement) and the shared ``model_dir``
(checkpoint handoff). Kill a worker — SIGTERM gets a grace checkpoint
and an immediate departure notice, SIGKILL is detected by heartbeat
loss — and the survivors bump the topology generation, reshard the
params/optimizer state onto the new dp width through the rule-driven
shard fns, and resume at the exact rng/iterator position. Launch a
replacement with the same command line and it joins the next
generation. Runbook: doc/elastic_runbook.md; chaos proof:
tools/smoke_elastic.py.

Usage (one invocation per worker, same config + shared dirs):

  CXXNET_CPU_DEVICES=2 CXXNET_RUN_ID=myrun \\
  python elastic_worker.py ../synthetic_mlp.conf \\
      elastic_dir=/shared/elastic elastic_worker=0 elastic_capacity=2 \\
      model_dir=/shared/models telemetry_host=0 \\
      telemetry_ledger=/shared/run.jsonl [key=value ...]

``elastic_capacity`` is the dp width this worker can host (defaults
to its local device count); the live member with the largest capacity
leads, the rest are warm standbys. On real TPU fleets drop
CXXNET_CPU_DEVICES and point ``dev=tpu`` at the local slice.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

n_cpu = int(os.environ.get("CXXNET_CPU_DEVICES", "0"))
if n_cpu:
    from cxxnet_tpu.parallel import force_cpu_devices
    force_cpu_devices(n_cpu)

from cxxnet_tpu.main import main

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
