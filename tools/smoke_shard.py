#!/usr/bin/env python
"""CPU smoke: rule-driven sharding on a dp x tp mesh.

On 8 faked CPU devices, runs a dp=4 x tp=2 round of the reduced
Inception-BN flagship through the RULE-DRIVEN partition specs,
asserting:

  1. params place per the rule table (a planned conv weight is
     model-sharded on the mesh);
  2. the traced step holds no Pallas kernel (GSPMD shards XLA's own
     code; sync-BN is the partitioner's all-reduce);
  3. the flagship's first-step loss on the mesh matches one device;
  4. a 5-step mesh run tracks the single-device run.

Wired into the verify recipe (.claude/skills/verify/SKILL.md
"sharding rules").
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "ImageNet"))

import jax  # noqa: E402

from cxxnet_tpu.parallel import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import numpy as np  # noqa: E402

from gen_inception_bn import generate  # noqa: E402

from cxxnet_tpu.config import parse_config_string  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.parallel import make_mesh_context  # noqa: E402
from cxxnet_tpu.trainer import Trainer  # noqa: E402


def main() -> int:
    assert len(jax.devices()) == 8, jax.devices()
    txt = generate(scale=0.25, image_size=64, num_class=8, batch_size=8,
                   with_data=False)
    cfg = parse_config_string(txt) + [
        ("eval_train", "0"), ("compute_dtype", "float32"),
        # small LR: the parity check compares 5-step trajectories, and
        # batch-8 BN training is chaotic enough at eta=0.01 that even
        # two CORRECT configurations (e.g. jnp dp8 vs jnp dp4xtp2)
        # drift ~1e-2 by step 3 from float association alone
        ("eta", "0.001")]
    rng = np.random.RandomState(0)
    data = (rng.randint(0, 32, (8, 64, 64, 3)) * 0.25).astype(np.float32)
    label = rng.randint(0, 8, (8, 1)).astype(np.float32)

    def batch():
        return DataBatch(data=data.copy(), label=label.copy())

    ctx = make_mesh_context(devices=jax.devices()[:8], model_parallel=2)
    tr = Trainer(cfg, mesh_ctx=ctx)
    tr.init_model()

    # -- 1. rule-driven placement: planned weights are model-sharded ----
    pspecs = tr.net.param_pspecs()
    sharded = [(name, tuple(spec)) for name, sub in pspecs.items()
               for key, spec in (sub.items()
                                 if isinstance(sub, dict) else [])
               if any(ax == "model" for ax in spec)]
    assert sharded, "rule table produced no model-sharded leaf"
    probe_name = next(name for name, _ in sharded
                      if hasattr(tr.params.get(name, {}), "get"))
    w = tr.params[probe_name]["wmat"]
    assert not w.sharding.is_fully_replicated, \
        f"{probe_name}/wmat not sharded on the mesh"
    print(f"smoke_shard: rule-driven specs place {len(sharded)} "
          f"model-sharded leaves (e.g. {probe_name}/wmat "
          f"{tuple(pspecs[probe_name]['wmat'])})")

    # -- 2. no kernel in the traced step --------------------------------
    step, args = tr._train_step_call(tr.stage_batch(batch()))
    jx = str(jax.make_jaxpr(step)(*args))
    assert "pallas_call" not in jx, "a Pallas kernel in the mesh step"
    print("smoke_shard: traced dp x tp train step holds no pallas_call")

    # -- 3. flagship: first-step loss parity vs single device ---------
    # (5-step trajectories of THIS model diverge ~1e-1 between two
    # CORRECT configs — e.g. pure-jnp dp8 vs single drifts 0.19 by
    # step 3 from GSPMD reduction association alone — so the flagship
    # pins the pre-update forward, and the trajectory check below runs
    # on a model without that chaos amplification)
    tr.update(batch())
    tr1 = Trainer(cfg, mesh_ctx=make_mesh_context(
        devices=jax.devices()[:1]))
    tr1.init_model()
    tr1.update(batch())
    d0 = abs(float(tr.last_loss) - float(tr1.last_loss))
    assert d0 < 1e-3, (float(tr.last_loss), float(tr1.last_loss))
    print(f"smoke_shard: flagship step-1 loss parity ok "
          f"(d={d0:.1e})")

    # -- 4. 5-step parity vs the single-device run ----------------------
    conv_cfg = parse_config_string("""
netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->2] = batch_norm:bn1
layer[2->3] = relu:r1
layer[3->4] = max_pooling:mp1
  kernel_size = 2
  stride = 2
layer[4->5] = flatten:fl
layer[5->6] = fullc:fc
  nhidden = 4
  init_sigma = 0.01
layer[6->6] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
eta = 0.05
eval_train = 0
compute_dtype = float32
""")
    cdata = (rng.randint(0, 16, (8, 8, 8, 3)) * 0.25).astype(np.float32)
    clabel = rng.randint(0, 4, (8, 1)).astype(np.float32)

    def crun(devs, mp=1):
        t = Trainer(conv_cfg, mesh_ctx=make_mesh_context(
            devices=jax.devices()[:devs], model_parallel=mp))
        t.init_model()
        out = []
        for _ in range(5):
            t.update(DataBatch(data=cdata.copy(), label=clabel.copy()))
            out.append(float(t.last_loss))
        return out
    losses_m = crun(8, mp=2)
    losses_1 = crun(1)
    for i, (a, b) in enumerate(zip(losses_m, losses_1)):
        assert abs(a - b) < 5e-3, (i, losses_m, losses_1)
    print(f"smoke_shard: 5-step dp x tp parity ok "
          f"(mesh {losses_m[-1]:.4f} vs single {losses_1[-1]:.4f})")
    print("smoke_shard ok: rule-driven sharding on a dp x tp mesh")
    return 0


if __name__ == "__main__":
    sys.exit(main())
