#!/usr/bin/env python
"""Cross-process ring-attention smoke: the 'seq' mesh axis spans every
process (sequence parallelism over DCN, the long-context scaling path).
Each worker holds its sequence shard; k/v shards travel the ring via
ppermute across process boundaries; every rank checks its local output
shards against the single-device reference.

Usage (one invocation per process):
  python ring_worker.py <coordinator host:port> <num_proc> <rank>
Set CXXNET_CPU_DEVICES for virtual CPU devices per process.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

# local simulation only when requested (same gating as worker.py): on real
# pod hosts leave the platform alone so the 'seq' axis spans actual TPUs
n_cpu = int(os.environ.get("CXXNET_CPU_DEVICES", "0"))
import jax
if n_cpu:
    from cxxnet_tpu.parallel import force_cpu_devices
    force_cpu_devices(n_cpu)


def main() -> int:
    coord, nproc, rank = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=rank)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from cxxnet_tpu.ops import attention_reference
    from cxxnet_tpu.parallel.ring import ring_attention_sharded

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("seq",))
    rng = np.random.RandomState(0)              # identical on every rank
    B, S, H, D = 2, 16 * len(devs), 2, 16
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        out = ring_attention_sharded(mesh, q, k, v, causal=causal)
        ref = np.asarray(attention_reference(q, k, v, causal=causal))
        worst = 0.0
        for sh in out.addressable_shards:       # local sequence shards only
            sl = sh.index[1]
            worst = max(worst, float(np.max(np.abs(
                np.asarray(sh.data) - ref[:, sl]))))
        assert worst < 1e-4, f"rank {rank} causal={causal} maxerr {worst}"
        if rank == 0:
            print(f"ring-attention x{nproc}proc causal={causal} "
                  f"ok: maxerr={worst:.2e}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
