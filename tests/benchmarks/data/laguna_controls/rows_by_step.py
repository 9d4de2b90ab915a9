"""One seed of the cell outside the harness: 62 steps (or ``<steps>``) of
Trainer.update on the cell's own staged batch, each step timed to its loss and the expert
layers' stats read after it. Not a run of the benchmark.

    chiprun -- python3 tests/benchmarks/data/laguna_controls/rows_by_step.py \\
        <seed> chiprun_out/rows_<seed>.json [<steps>]

(PERF.md section 6, PR 32: why the cell's rate differs by seed.)
"""
import json, os, sys, time
ROOT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[".."] * 4))
os.chdir(ROOT)
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
seed, out = int(sys.argv[1]) % (2 ** 31 - 1), sys.argv[2]
STEPS = int(sys.argv[3]) if len(sys.argv) > 3 else 62
import numpy as np
from benchmarks import run as R
with open("benchmarks/config_defaults.json") as f:
    cfg = json.load(f)["optional_keys"]
cfg_path = os.path.join(ROOT, "benchmarks/configs/laguna_s_2_1.json")
with open(cfg_path) as f:
    cfg.update(json.load(f))
with open("benchmarks/traffic/resident_tokens_8k.json") as f:
    traffic = json.load(f)
feed_mod = R.load_module("benchmarks/feeds/resident_tokens.py")
ctx = {"seed": seed, "chips": 1, "rows": 1, "root": ROOT,
       "input_shape": tuple(cfg["input_shape"]), "config": cfg,
       "cache_dir": "benchmarks/.cache", "say": print}
from cxxnet_tpu.main import LearnTask
pairs = R.build_pairs(cfg, cfg_path, "tpu:0", seed, 1,
                      feed_mod.section(traffic, ctx),
                      "benchmarks/.cache/models")
task = LearnTask(pairs)
tr = task.trainer
task._init_model()
feed = feed_mod.open(task, tr, traffic, ctx)
batch = feed.staged
names = [n for n in tr.net_state if "stats" in tr.net_state[n]]
rows = []
for step in range(STEPS):
    t0 = time.perf_counter()
    tr.update(batch)
    loss = float(tr.last_loss)
    dt = time.perf_counter() - t0
    st = {n: [float(v) for v in np.asarray(tr.net_state[n]["stats"])]
          for n in names}
    rows.append({"step": step, "s": dt, "loss": loss, "stats": st})
    if step % 10 == 0 or step == STEPS - 1:
        print(step, round(dt, 4), round(loss, 5),
              {n: (int(v[0]), int(v[5]), round(v[3], 2)) for n, v in st.items()},
              flush=True)
late = [r["s"] for r in rows[12:]]
print("seed", seed, f"mean step s over steps 12..{STEPS - 1}",
      sum(late) / len(late))
os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
json.dump({"seed": seed, "rows": rows}, open(out, "w"))
