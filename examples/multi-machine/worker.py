#!/usr/bin/env python
"""One worker process of a multi-host run (reference analog: a ps-lite /
rabit worker launched by dmlc_mpi.py — example/multi-machine/run.sh).

Usage (one invocation per process, same config):
  python worker.py <config.conf> dist_coordinator=host:port \
      dist_num_proc=N dist_rank=i [key=value ...]

For a local simulation ('ps-lite local.sh' analog) set CXXNET_CPU_DEVICES
to give each process that many virtual CPU devices; see local_launch.sh.
jax.distributed.initialize is called by the task driver from the dist_*
config keys before any device is touched, so jax.devices() spans all
processes and the data-parallel mesh covers the whole job.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

n_cpu = int(os.environ.get("CXXNET_CPU_DEVICES", "0"))
if n_cpu:
    import jax
    from cxxnet_tpu.parallel import force_cpu_devices
    force_cpu_devices(n_cpu)

from cxxnet_tpu.main import main

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
