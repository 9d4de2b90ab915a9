"""The ``joyai_llm_flash`` configuration in the benchmark (PR 28): its
file and entries, its reference module's ``check`` against the program
at the toy size — sound, and with each control's fault planted, which
has to come out not correct — its pinned operation count, its cell
walked by the CPU rehearsal, and each of its readers on a hand-made
view. On the CPU backend at a toy size: no number here is a device
number."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
TOY = os.path.join(DATA, "joyai_toy")
CONTROLS = ["float8", "weights_off", "top7", "no_shared", "no_scaling",
            "bias_in_weights", "rope_halves", "scale_nope"]
CELL = "joyai_ep16_train_8k"
NEW_METRICS = {"moe_route_ms_per_step", "moe_experts_ms_per_step",
               "mla_attend_ms_per_step", "head_loss_ms_per_step",
               "moe_experts_roofline_pct", "mla_attend_roofline_pct",
               "moe_load_max_over_mean"}


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(variant=None):
    if variant is None:
        return load(os.path.join(BENCH, "references", "joyai_llm_flash.py"),
                    "bench_joyai_ref")
    return load(os.path.join(DATA, "joyai_controls", "references",
                             f"joyai_llm_flash_{variant}.py"),
                "bench_joyai_ref_" + variant)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "joyai_llm_flash.json")) as f:
        return json.load(f)


# -- the file and the entries -------------------------------------------------


def test_the_file_keeps_every_published_width(config):
    published = {
        "hidden_size": 2048, "intermediate_size": 7168,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 32, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
        "v_head_dim": 128, "head_dim": 64, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.5, "rope_theta": 32000000,
        "rms_norm_eps": 1e-06, "num_nextn_predict_layers": 1,
        "max_position_embeddings": 131072, "n_group": 1, "topk_group": 1}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["scoring_func"] == "sigmoid"
    assert config["topk_method"] == "noaux_tc"
    assert config["norm_topk_prob"] is True
    assert config["rope_interleave"] is True
    # the cut, each beside what was published, and the deployment
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert config["n_routed_experts_published"] == 256
    assert "16 chips share each layer" in config["deployment"]
    assert any("bias_update_rate" in a for a in config["assumed"])
    assert any("mtp_loss_weight" in a for a in config["assumed"])
    assert any(a.startswith("eta") for a in config["assumed"])
    assert "overrides" not in config


def test_the_conf_is_the_generators_output(config):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import gen_joyai_conf
    finally:
        sys.path.pop(0)
    with open(os.path.join(BENCH, "configs", "joyai_llm_flash.conf")) as f:
        assert f.read() == gen_joyai_conf.conf(config)
    with open(os.path.join(TOY, "configs", "joyai_toy.json")) as f:
        toy = json.load(f)
    with open(os.path.join(TOY, "configs", "joyai_toy.conf")) as f:
        assert f.read() == gen_joyai_conf.conf(toy)


def test_the_configuration_entry(manifest):
    """The new configuration's own entry, and its file beside it."""
    entry = manifest["configs"][-1]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["name"] == "joyai_llm_flash"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        held = json.load(f)
    assert held["name"] == entry["name"]
    assert held["source"] == entry["source"]
    assert held["reduced"] == entry["reduced"]
    assert held["items_per_row"] == held["input_shape"][-1]


def test_the_entries(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells == ["ibn_resident", "alexnet_resident", "ibn_dp4", CELL]
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai_llm_flash", "resident_tokens_8k", 1)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_items_per_s_chip"
    assert [m["name"] for m in manifest["per_layer"][-7:]] == [
        "moe_route_ms_per_step", "moe_experts_ms_per_step",
        "mla_attend_ms_per_step", "head_loss_ms_per_step",
        "moe_experts_roofline_pct", "mla_attend_roofline_pct",
        "moe_load_max_over_mean"]
    # the one touch to an entry that was there
    assert by_name["conv_mxu_pct"]["workloads"] == [
        "ibn_resident", "alexnet_resident", "ibn_dp4"]
    with open(os.path.join(BENCH, "traffic", "resident_tokens_8k.json")) as f:
        mix = json.load(f)
    assert (mix["feed"], mix["rows_per_chip"]) == ("resident_tokens", 1)


# -- the operation count --------------------------------------------------------


def test_the_operation_count_is_pinned(config):
    ref = reference()
    assert ref.matrix_params_per_position(config) == 314703872.0
    assert ref.attention_flops(config, 8192) == 687278653440.0
    assert ref.attention_layers(config) == 6
    view = {"config": config, "rows": 1}
    assert ref.train_step_flops(view) == 27839340478464.0
    # per position, and the two parts the issue reckons apart
    assert ref.train_step_flops(view) / 8192 == 3398356992.0
    assert 6 * 8192 * 314703872.0 == 15468324716544.0
    assert 3 * 6 * 687278653440.0 == 12371015761920.0
    # the held experts count by the EXPECTED pairs: 8 x 16 / 256 a position
    half = dict(config, n_routed_experts=8)
    assert ref.matrix_params_per_position(config) \
        - ref.matrix_params_per_position(half) \
        == 5 * 3 * 2048 * 768 * (8 * 8 / 256)
    assert ref.expert_pair_flops(config) == 2.0 * 3 * 2048 * 768


# -- check() against the program, sound and with each fault planted ---------------


@pytest.fixture(scope="module")
def trained():
    """The toy configuration through the program's own update path: six
    steps on one staged batch, as the harness's warm-up makes them."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    with open(os.path.join(TOY, "configs", "joyai_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "joyai_toy.conf")) as f:
        text = f.read()
    rows, S, V = 2, cfg["positions"], cfg["vocab_size"]
    tr = Trainer(parse_config_string(
        text + f"dev = cpu:0\nseed = 11\nbatch_size = {rows}\n"))
    tr.init_model()
    rng = np.random.RandomState(11)
    toks = rng.randint(0, V, (rows, S))
    batch = DataBatch(
        data=toks.astype(np.float32).reshape(rows, 1, 1, S),
        label=((toks + toks[:, :1]) % V).astype(np.float32))
    losses = []
    for _ in range(6):
        tr.update(batch)
        losses.append(float(tr.last_loss))
    return {"config": cfg, "layers": tr.graph.layers,
            "defaults": dict(tr.graph.defcfg), "trainer": tr,
            "params0": None, "batch0": batch, "warm_losses": losses,
            "dtype": "float32", "rows": rows, "chips": 1,
            "say": lambda **fields: print(fields)}


def test_check_holds_the_program_to_the_reference(trained):
    ok, said = reference().check("train_steps", trained)
    over = {k: v for k, v in said.items()
            if k.endswith("_diff") and v > said[k + "_limit"]}
    assert ok and not over, over
    # every number compared stands beside its limit
    compared = [k for k in said if k.endswith("_diff")]
    assert {"loss_step1_abs_diff", "loss_step2_abs_diff",
            "loss_step3_abs_diff", "probe_loss_abs_diff",
            "probe_loss_main_abs_diff", "probe_loss_mtp_abs_diff",
            "grad_norm_embed_rel_diff",
            "grad_norm_head_rel_diff", "grad_norm_routers_rel_diff",
            "grad_norm_w_eh_rel_diff", "grad_norm_b0_rel_diff",
            "grad_norm_b1_rel_diff", "grad_norm_mtp_rel_diff"} \
        <= set(compared)
    assert all(k + "_limit" in said for k in compared)
    assert said["moe_pairs_dropped"] == 0.0
    # the step's own losses fell on the repeated batch; the probe's is
    # both heads', the second weighed
    assert trained["warm_losses"][-1] < trained["warm_losses"][0]
    assert said["probe_loss_program"] == pytest.approx(
        said["probe_loss_main_program"] + trained["config"][
            "mtp_loss_weight"] * said["probe_loss_mtp_program"], abs=2e-6)
    # the probe was one more step of the trainer's own update, and the
    # routers have their own selection bias back
    tr = trained["trainer"]
    assert int(tr.opt_state["t"]) >= 7
    assert float(np.abs(np.asarray(
        tr.net_state["b1_moe"]["sel_bias"])).max()) < 0.02


@pytest.mark.parametrize("variant", CONTROLS)
def test_each_control_comes_out_not_correct(trained, variant):
    mod = reference(variant)
    assert mod.ref.VARIANT == variant
    ok, said = mod.check("train_steps", trained)
    over = [k for k, v in said.items()
            if k.endswith("_diff") and not v <= said[k + "_limit"]]
    assert not ok and over, said
    assert said["variant"] == variant
    assert mod.train_step_flops is mod.ref.train_step_flops


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "joyai_llm_flash.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(cxxnet_tpu|benchmarks)",
                         text, re.M)


# -- the cell, walked by the rehearsal ---------------------------------------------


def test_the_toy_cell_walks_the_harness():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(TOY, "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", "joyai_toy_resident", "--seed", "3000000019",
         "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["compared"]["moe_pairs_dropped"] == 0.0
    picked = [line for line in lines if "fused_kernels" in line][0]
    assert picked["fused_kernels"]["grouped"] == {"ragged_dot": 2}
    assert picked["fused_kernels"]["attention"] == {"mla.ref": 3}
    steps = [line for line in lines if "items" in line][0]
    assert steps["items"] == steps["steps"] * 2 * 32      # positions


# -- the readers, each on a hand-made view ---------------------------------------------


@pytest.fixture()
def view(monkeypatch):
    """Two steps of a device trace with one instruction under each
    sub-scope, and the program's tables and counters planted."""
    from benchmarks import joyai_reads, program_reads
    from cxxnet_tpu.telemetry.traceparse import classify
    table = {
        "fusion.1": "jit(one)/jvp(b1_moe)/moe.route/top_k",
        "fusion.2": "jit(one)/transpose(jvp(b1_moe))/moe.experts/ragged_dot",
        "fusion.3": "jit(one)/jvp(b1_moe)/checkpoint/moe.experts/ragged_dot",
        "custom-call.4": "jit(one)/jvp(b0_attn)/mla.attend/pallas_call",
        "fusion.5": "jit(one)/jvp(loss_main)/head_loss/log_softmax",
        "fusion.6": "jit(one)/jvp(b0_attn)/mla.proj/dot_general",
        "fusion.7": "jit(one)/optimizer/add",
    }
    monkeypatch.setattr(program_reads, "_program", lambda: (table, classify))
    monkeypatch.setattr(joyai_reads, "_program", lambda: (table, classify))
    counters = {"cxxnet_moe_pairs_held_total": 10 * 5 * 3000.0,
                "cxxnet_moe_steps_total": 10.0,
                "cxxnet_moe_pairs_held_last_step": 5 * 4096.0}
    monkeypatch.setattr(joyai_reads, "counter", counters.get)
    monkeypatch.setattr(joyai_reads, "gauge_max", lambda name: 1.25)
    by_name = {"fusion %fusion.1": 0.002, "fusion %fusion.2": 0.006,
               "fusion %fusion.3": 0.004, "custom-call %custom-call.4": 0.3,
               "fusion %fusion.5": 0.010, "fusion %fusion.6": 0.5,
               "fusion %fusion.7": 0.05}
    return {"trace": {"devices": [{"by_name": by_name, "steps": 2}]},
            "rows": 1, "chips": 1, "peaks": {"bf16_tflops": 197.0},
            "spans": [], "span_window_s": 0.0, "step_flops": 1.0,
            "compiles_in_window": 0}


def reader(name):
    return load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                "bench_reader_" + name)


def test_the_time_readers(view):
    assert reader("moe_route_ms_per_step").read(view) \
        == pytest.approx(1.0)
    assert reader("moe_experts_ms_per_step").read(view) \
        == pytest.approx(5.0)       # backward and the rebuilt forward
    assert reader("mla_attend_ms_per_step").read(view) \
        == pytest.approx(150.0)
    assert reader("head_loss_ms_per_step").read(view) \
        == pytest.approx(5.0)
    assert reader("moe_load_max_over_mean").read(view) == 1.25


def test_the_roofline_readers(view, config):
    ref = reference()
    # 5 layers x 4096 pairs at the last drained step (the run's mean
    # was 3000: the routing drifts), forward once and backward twice,
    # over 5 ms a traced step
    want = 3 * 5 * 4096 * ref.expert_pair_flops(config) / 5e-3 / 197e12
    assert reader("moe_experts_roofline_pct").read(view) \
        == pytest.approx(100 * want)
    want = 3 * 6 * ref.attention_flops(config, 8192) / 0.15 / 197e12
    assert reader("mla_attend_roofline_pct").read(view) \
        == pytest.approx(100 * want)
    assert 0 < 100 * want < 100


def test_the_readers_find_nothing_in_a_program_without_the_scopes(
        view, monkeypatch):
    from benchmarks import joyai_reads
    monkeypatch.setattr(joyai_reads, "_program", lambda: ({}, None))
    monkeypatch.setattr(joyai_reads, "counter", lambda name: None)
    monkeypatch.setattr(joyai_reads, "gauge_max", lambda name: None)
    for name in NEW_METRICS:
        assert reader(name).read(view) is None, name
    # and nothing without a device trace
    monkeypatch.undo()
    for name in NEW_METRICS - {"moe_load_max_over_mean"}:
        assert reader(name).read(dict(view, trace=None)) is None


def test_the_counters_come_with_the_train_metric(trained):
    from benchmarks.joyai_reads import counter, gauge_max
    steps = counter("cxxnet_moe_steps_total")
    # update() drains the step before: five of the six are in
    assert steps >= 5
    held = counter("cxxnet_moe_pairs_held_total")
    elsewhere = counter("cxxnet_moe_pairs_elsewhere_total")
    assert counter("cxxnet_moe_pairs_dropped_total") == 0
    # two expert layers (one in the stack, one in the module), 2 x 32
    # positions, 3 experts a position
    assert held + elsewhere == steps * 2 * 2 * 32 * 3
    assert 0 < held < elsewhere                 # 4 of 16 experts held
    assert gauge_max("cxxnet_moe_load_max_over_mean") > 0
    # the gauges are the last drained step's: the checks' probes ran
    # under a planted bias, one more step runs under the routers' own
    # (a thousandth a step)
    tr = trained["trainer"]
    tr.update(trained["batch0"])
    tr.train_metric_report()
    assert 0 < gauge_max("cxxnet_moe_sel_bias_absmax") < 0.02
    assert counter("cxxnet_moe_steps_total") == steps + 1
    assert 0 < counter("cxxnet_moe_pairs_held_last_step") \
        == counter("cxxnet_moe_pairs_held_total") - held
