"""The ``laguna_s_2_1`` configuration in the benchmark (PR 32): its file
and entries, its reference module's ``check`` against the program at the
toy size — sound, and with each control's fault planted, which has to
come out not correct — its pinned operation count, its cell walked by
the CPU rehearsal, and each of its readers on a hand-made view. On the
CPU backend at a toy size: no number here is a device number. Entries
are found by name, not by place: a later configuration goes after
them."""

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
TOY = os.path.join(DATA, "laguna_toy")
CONTROLS = ["float8", "top9", "sigmoid_scores", "no_renorm", "no_scaling",
            "no_shared", "window_off", "window_511", "kv_mod", "no_gate",
            "rope_full", "no_yarn", "no_attention_factor"]
CELL = "laguna_ep32_train_8k"
NEW_METRICS = ["gqa_full_attend_ms_per_step", "gqa_window_attend_ms_per_step",
               "gqa_full_attend_roofline_pct",
               "gqa_window_attend_roofline_pct",
               "laguna_moe_route_ms_per_step",
               "laguna_moe_experts_ms_per_step",
               "laguna_moe_experts_roofline_pct",
               "laguna_moe_load_max_over_mean",
               "laguna_head_loss_ms_per_step"]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_attention_heads", "num_key_value_heads",
           "num_attention_heads_per_layer"]
PERIOD = ["full_attention"] + ["sliding_attention"] * 3


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(variant=None):
    if variant is None:
        return load(os.path.join(BENCH, "references", "laguna_s_2_1.py"),
                    "bench_laguna_ref")
    return load(os.path.join(DATA, "laguna_controls", "references",
                             f"laguna_s_2_1_{variant}.py"),
                "bench_laguna_ref_" + variant)


def conf_tool():
    return load(os.path.join(ROOT, "tools", "gen_joyai_conf.py"),
                "gen_conf_for_laguna_tests")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "laguna_s_2_1.json")) as f:
        return json.load(f)


# -- the file and the entries -------------------------------------------------


def test_the_file_keeps_every_published_width(config):
    published = {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "head_dim": 128,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "num_experts_per_tok": 10,
        "sliding_window": 512, "max_position_embeddings": 1048576,
        "rms_norm_eps": 1e-06, "decoder_sparse_step": 1,
        "mlp_only_layers": [0], "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0, "gating": "per-head",
        "attention_bias": False, "norm_topk_prob": True,
        "tie_word_embeddings": False,
        "moe_apply_router_weight_on_input": False}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    # the lists by layer are kept whole and read up to the depth
    assert config["layer_types"] == PERIOD * 12
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert config["gating_types"] == ["per_head"] * 48
    # the cut, each beside what was published, and the deployment
    assert config["reduced"] == REDUCED
    assert [config[k] for k in REDUCED] == [5, 8, 12544, 24, 4,
                                            [24, 36, 36, 36, 24]]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12}
    # the floors: a whole period after the leading dense layer, 8 of 256
    # experts, an eighth of the vocabulary; each layer's heads over a pair
    assert config["layer_types"][:5] == PERIOD + ["full_attention"]
    assert config["num_experts_published"] == 256
    assert config["vocab_size"] * 8 == 100352
    assert [2 * h for h in config["num_attention_heads_per_layer"]] \
        == config["published"]["num_attention_heads_per_layer"][:5]
    first = config["expert_first"]
    assert first > 0 and first % 8 == 0 and first + 8 <= 256
    for said in ("32 that share each layer", "pair of chips",
                 "12544 of 100352", "partial sum"):
        assert said in config["deployment"], said
    for said in ("softmax as the router's score function", "q/k norm",
                 "arXiv:2505.06708", "the window counts the position",
                 "attention_factor scales", "eta 0.0001", "init_sigma",
                 "remat = 1", "1 row of 8192"):
        assert any(said in a for a in config["assumed"]), said
    assert "overrides" not in config


def test_the_conf_is_the_generators_output(config):
    tool = conf_tool()
    with open(os.path.join(BENCH, "configs", "laguna_s_2_1.conf")) as f:
        text = f.read()
    assert text == tool.conf(config)
    assert text.count("= gqa:") == 5 and text.count("= moe:") == 4
    assert text.count("window = 512") == 3 and text.count("window = 0") == 2
    assert text.count("router = softmax_nodrop") == 4
    assert text.count("rope_type = yarn") == 2
    with open(os.path.join(TOY, "configs", "laguna_toy.json")) as f:
        toy = json.load(f)
    with open(os.path.join(TOY, "configs", "laguna_toy.conf")) as f:
        assert f.read() == tool.conf(toy)


def test_the_changed_conf_tool_writes_the_first_family_as_before():
    tool = conf_tool()
    for stem in (os.path.join(BENCH, "configs", "joyai_llm_flash"),
                 os.path.join(DATA, "joyai_toy", "configs", "joyai_toy")):
        with open(stem + ".json") as f:
            cfg = json.load(f)
        with open(stem + ".conf") as f:
            assert f.read() == tool.conf(cfg), stem


def test_the_configuration_entry(manifest):
    entry = [c for c in manifest["configs"] if c["name"] == "laguna_s_2_1"]
    assert len(entry) == 1
    entry = entry[0]
    assert len(manifest["configs"]) >= 4
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"] == ("https://huggingface.co/poolside/"
                               "Laguna-S-2.1/blob/main/config.json")
    assert entry["reduced"] == REDUCED
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        held = json.load(f)
    assert held["name"] == entry["name"]
    assert held["source"] == entry["source"]
    assert held["reduced"] == entry["reduced"]
    assert held["items_per_row"] == held["input_shape"][-1] == 8192


def test_the_entries(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[:5] == ["ibn_resident", "alexnet_resident", "ibn_dp4",
                         "joyai_ep16_train_8k", CELL]
    cell = manifest["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_s_2_1", "resident_tokens_8k", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_items_per_s_chip"
        assert by_name[name]["layer"] == "kernels"
        assert name.endswith("_roofline_pct") == (
            by_name[name]["unit"] == "%")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 9] == NEW_METRICS
    assert names[at - 1] == "moe_load_max_over_mean"    # appended
    # no entry that was there was touched: the first cell-bound metrics
    # still list the cells they listed
    assert by_name["conv_mxu_pct"]["workloads"] == [
        "ibn_resident", "alexnet_resident", "ibn_dp4"]
    assert by_name["mla_attend_ms_per_step"]["workloads"] == [
        "joyai_ep16_train_8k"]


# -- the operation count --------------------------------------------------------


def test_the_operation_count_is_pinned(config):
    ref = reference()
    assert ref.attended_pairs(config, 8192, "full_attention") \
        == 8192 * 8193 / 2
    assert ref.attended_pairs(config, 8192, "sliding_attention") \
        == 512 * 513 / 2 + (8192 - 512) * 512
    assert ref.attention_layers(config, "full_attention") == [24, 24]
    assert ref.attention_layers(config, "sliding_attention") == [36, 36, 36]
    assert ref.attention_flops(config, 8192, "full_attention") \
        == 824734384128.0
    assert ref.attention_flops(config, 8192, "sliding_attention") \
        == 224694632448.0
    assert ref.matrix_params_per_position(config) == 343363584.0
    view = {"config": config, "rows": 1}
    assert ref.train_step_flops(view) == 20025293930496.0
    # the parts the issue reckons apart: matrices 16.9 (16.3 and the held
    # experts' 0.6), attention 3.1 of which the window layers 0.7 (TFLOP
    # a step)
    assert 6 * 8192 * 343363584.0 == 16877006880768.0
    assert 3 * (824734384128.0 + 224694632448.0) == 3148287049728.0
    # the held experts count by the EXPECTED pairs: 10 x 8 / 256 a position
    half = dict(config, num_experts=4)
    assert ref.matrix_params_per_position(config) \
        - ref.matrix_params_per_position(half) \
        == 4 * 3 * 3072 * 1024 * (10 * 4 / 256)
    assert ref.expert_pair_flops(config) == 2.0 * 3 * 3072 * 1024


# -- check() against the program, sound and with each fault planted ---------------


@pytest.fixture(scope="module")
def trained():
    """The toy configuration through the program's own update path: six
    steps on one staged batch, as the harness's warm-up makes them."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    with open(os.path.join(TOY, "configs", "laguna_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "laguna_toy.conf")) as f:
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
            "probe_loss_metric_abs_diff", "grad_norm_embed_rel_diff",
            "grad_norm_head_rel_diff", "grad_norm_routers_rel_diff",
            "grad_norm_gates_rel_diff", "grad_norm_b0_rel_diff",
            "grad_norm_b1_rel_diff", "grad_norm_b2_rel_diff"} \
        <= set(compared)
    assert all(k + "_limit" in said for k in compared)
    assert said["moe_pairs_dropped"] == 0.0
    assert trained["warm_losses"][-1] < trained["warm_losses"][0]
    # the probe was one more step of the trainer's own update
    assert int(trained["trainer"].opt_state["t"]) >= 7


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
    with open(os.path.join(BENCH, "references", "laguna_s_2_1.py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(cxxnet_tpu|benchmarks)",
                         text, re.M)
    assert "joyai_llm_flash" not in text


# -- the cell, walked by the rehearsal ---------------------------------------------


def test_the_toy_cell_walks_the_harness():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(TOY, "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", "laguna_toy_resident", "--seed", "3000000019",
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
    assert picked["fused_kernels"]["attention"] == {"gqa.ref": 3}
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
        "custom-call.4": "jit(one)/jvp(b0_attn)/gqa.attend.full/pallas_call",
        "custom-call.5": "jit(one)/transpose(jvp(b1_attn))/"
                         "gqa.attend.window/pallas_call",
        "fusion.6": "jit(one)/transpose(jvp(b1_attn))/gqa.attend.window/"
                    "reduce_sum",
        "fusion.7": "jit(one)/jvp(loss_main)/head_loss/log_softmax",
        "fusion.8": "jit(one)/jvp(b0_attn)/gqa.proj/dot_general",
        "fusion.9": "jit(one)/jvp(b0_attn)/gqa.gate/logistic",
    }
    monkeypatch.setattr(program_reads, "_program", lambda: (table, classify))
    monkeypatch.setattr(joyai_reads, "_program", lambda: (table, classify))
    counters = {"cxxnet_moe_pairs_held_last_step": 4 * 2560.0}
    monkeypatch.setattr(joyai_reads, "counter", counters.get)
    monkeypatch.setattr(joyai_reads, "gauge_max", lambda name: 1.5)
    by_name = {"fusion %fusion.1": 0.002, "fusion %fusion.2": 0.006,
               "fusion %fusion.3": 0.004, "custom-call %custom-call.4": 0.1,
               "custom-call %custom-call.5": 0.05, "fusion %fusion.6": 0.002,
               "fusion %fusion.7": 0.010, "fusion %fusion.8": 0.5,
               "fusion %fusion.9": 0.05}
    return {"trace": {"devices": [{"by_name": by_name, "steps": 2}]},
            "rows": 1, "chips": 1, "peaks": {"bf16_tflops": 197.0},
            "spans": [], "span_window_s": 0.0, "step_flops": 1.0,
            "compiles_in_window": 0}


def reader(name):
    return load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                "bench_reader_" + name)


def test_the_time_readers(view):
    want = {"gqa_full_attend_ms_per_step": 50.0,
            "gqa_window_attend_ms_per_step": 26.0,   # kernel and group sum
            "laguna_moe_route_ms_per_step": 1.0,
            "laguna_moe_experts_ms_per_step": 5.0,
            "laguna_head_loss_ms_per_step": 5.0}
    for name, ms in want.items():
        assert reader(name).read(view) == pytest.approx(ms), name
    assert reader("laguna_moe_load_max_over_mean").read(view) == 1.5


def test_the_roofline_readers(view, config):
    ref = reference()
    want = 3 * ref.attention_flops(config, 8192, "full_attention") \
        / 0.05 / 197e12
    assert reader("gqa_full_attend_roofline_pct").read(view) \
        == pytest.approx(100 * want)
    assert 0 < 100 * want < 100
    want = 3 * ref.attention_flops(config, 8192, "sliding_attention") \
        / 0.026 / 197e12
    assert reader("gqa_window_attend_roofline_pct").read(view) \
        == pytest.approx(100 * want)
    want = 3 * 4 * 2560 * ref.expert_pair_flops(config) / 5e-3 / 197e12
    assert reader("laguna_moe_experts_roofline_pct").read(view) \
        == pytest.approx(100 * want)


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
    for name in set(NEW_METRICS) - {"laguna_moe_load_max_over_mean"}:
        assert reader(name).read(dict(view, trace=None)) is None


def test_the_counters_come_with_the_train_metric(trained):
    """The ``cxxnet_moe_*`` family is fed by the softmax-scored layers
    too, and the tile gauges stand as the net was built."""
    from benchmarks.joyai_reads import _family, counter
    tr = trained["trainer"]
    before = counter("cxxnet_moe_steps_total") or 0
    held0 = counter("cxxnet_moe_pairs_held_total") or 0
    away0 = counter("cxxnet_moe_pairs_elsewhere_total") or 0
    tr.update(trained["batch0"])
    tr.update(trained["batch0"])
    tr.train_metric_report()
    steps = counter("cxxnet_moe_steps_total") - before
    assert steps >= 2
    held = counter("cxxnet_moe_pairs_held_total") - held0
    away = counter("cxxnet_moe_pairs_elsewhere_total") - away0
    # two expert layers, 2 x 32 positions, 3 experts a position
    assert held + away == steps * 2 * 2 * 32 * 3
    # 4 of 16 experts held; no bias and no auxiliary loss keeps the
    # shares apart on a repeated batch, so only that both exist
    assert held > 0 and away > 0
    assert counter("cxxnet_moe_pairs_dropped_total") == 0
    by_layer = {labels[0]: child.value for labels, child in _family(
        "cxxnet_moe_load_max_over_mean").samples()}
    assert by_layer["b1_moe"] > 0 and by_layer["b2_moe"] > 0
    bias = {labels[0]: child.value for labels, child in _family(
        "cxxnet_moe_sel_bias_absmax").samples()}
    assert bias["b1_moe"] == 0 and bias["b2_moe"] == 0      # no bias here
    # 32 positions are one tile: executed and total are both 1
    tiles = {labels[0]: child.value for labels, child in _family(
        "cxxnet_attn_tiles_executed").samples()}
    assert {"b0_attn", "b1_attn", "b2_attn"} <= set(tiles)
