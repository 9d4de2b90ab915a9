"""The ``keye_vl_2_0_30b_a3b`` configuration in the benchmark (PR 34): its
file and entries, its reference module's ``check`` against the program at
the toy size — sound, and with each control's fault planted, which has
to come out not correct — its pinned pair and operation counts, its cell
walked by the CPU rehearsal, and each of its readers on a hand-made view.
On the CPU backend at a toy size: no number here is a device number.
Entries are found by name, not by place: a later configuration goes
after them."""

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
TOY = os.path.join(DATA, "keye_toy")
NAME = "keye_vl_2_0_30b_a3b"
CONTROLS = ["float8", "select_off", "topk_half", "no_relu", "no_w",
            "no_index_loss", "index_attached", "p_attached", "no_qk_norm",
            "kv_mod", "sections_permuted", "top7", "no_renorm"]
CELL = "keye_ep16_train_8k"
NEW_METRICS = ["dsa_index_ms_per_step", "dsa_select_ms_per_step",
               "dsa_attend_ms_per_step", "dsa_index_roofline_pct",
               "dsa_attend_roofline_pct", "keye_moe_route_ms_per_step",
               "keye_moe_experts_ms_per_step",
               "keye_moe_experts_roofline_pct",
               "keye_moe_load_max_over_mean"]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_local_experts"]
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(variant=None):
    if variant is None:
        return load(os.path.join(BENCH, "references", NAME + ".py"),
                    "bench_keye_ref")
    return load(os.path.join(DATA, "keye_controls", "references",
                             f"{NAME}_{variant}.py"),
                "bench_keye_ref_" + variant)


def conf_tool():
    return load(os.path.join(ROOT, "tools", "gen_joyai_conf.py"),
                "gen_conf_for_keye_tests")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


# -- the file and the entries -------------------------------------------------


def test_the_file_keeps_every_published_width(config):
    published = {
        "model_type": "KeyeVL2", "hidden_size": 2048, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "intermediate_size": 6144, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "rope_theta": 10000000,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "rms_norm_eps": 1e-06, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "hidden_act": "silu",
        "attention_bias": False, "norm_topk_prob": True,
        "tie_word_embeddings": False, "sliding_window": None,
        "use_sliding_window": False}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_scaling"] == {
        "mrope_section": [16, 24, 24], "rope_type": "default",
        "type": "default"}
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert sum(config["rope_scaling"]["mrope_section"]) * 2 \
        == config["head_dim"]
    # the cut, each beside what was published, and the deployment
    assert config["reduced"] == REDUCED
    assert [config[k] for k in REDUCED] == [8, 8, 18992, 8]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936,
        "num_local_experts": 128}
    # the floors: at least four layers (the period is one), 8 of 128
    # experts in a range that is not the first, an eighth of the
    # vocabulary
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts_published"] == 128
    assert config["vocab_size"] * 8 == 151936
    first = config["expert_first"]
    assert first > 0 and first % 8 == 0 and first + 8 <= 128
    for said in ("16 that share each layer", "18992 of 151936",
                 "partial sum", "vision tower is not built",
                 "text positions"):
        assert said in config["deployment"], said
    for said in ("q/k norm", "contiguous, not interleaved",
                 "the indexer reads the layer's normed input",
                 "no Hadamard", "q_chunk_size", "arXiv:2512.02556",
                 "index_loss_coef 1", "a tie at the 2048th",
                 "no auxiliary balance loss", "eta 0.0001", "init_sigma",
                 "1 row of 8192", "remat = 1"):
        assert any(said in a for a in config["assumed"]), said
    assert config["index_loss_coef"] == 1.0
    assert "overrides" not in config and "vision_config" not in config


def test_the_conf_is_the_generators_output(config):
    tool = conf_tool()
    with open(os.path.join(BENCH, "configs", NAME + ".conf")) as f:
        text = f.read()
    assert text == tool.conf(config)
    assert text.count("= dsa:") == 8 and text.count("= moe:") == 8
    assert "= gqa:" not in text and "= ffn:" not in text
    assert text.count("index_topk = 2048") == 8
    assert text.count("mrope_section = 16,24,24") == 8
    assert text.count("qk_norm = 1") == 8
    assert text.count("router = softmax_nodrop") == 8
    assert text.count("shared_expert = 0") == 8
    assert text.count("expert_first = 40") == 8
    with open(os.path.join(TOY, "configs", "keye_toy.json")) as f:
        toy = json.load(f)
    with open(os.path.join(TOY, "configs", "keye_toy.conf")) as f:
        assert f.read() == tool.conf(toy)


@pytest.mark.parametrize("stem", [
    os.path.join(BENCH, "configs", "joyai_llm_flash"),
    os.path.join(DATA, "joyai_toy", "configs", "joyai_toy"),
    os.path.join(BENCH, "configs", "laguna_s_2_1"),
    os.path.join(DATA, "laguna_toy", "configs", "laguna_toy")])
def test_the_changed_conf_tool_writes_the_other_families_as_before(stem):
    with open(stem + ".json") as f:
        cfg = json.load(f)
    with open(stem + ".conf") as f:
        assert f.read() == conf_tool().conf(cfg), stem


def test_the_configuration_entry(manifest):
    entry = [c for c in manifest["configs"] if c["name"] == NAME]
    assert len(entry) == 1
    entry = entry[0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        held = json.load(f)
    assert held["name"] == entry["name"]
    assert held["source"] == entry["source"]
    assert held["reduced"] == entry["reduced"]
    assert held["items_per_row"] == held["input_shape"][-1] == 8192


def test_the_entries(manifest):
    cell = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(cell) == 1
    cell = cell[0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "resident_tokens_8k", 1)
    assert len(cell["why"]) <= 200
    # one cell of this configuration, and no four-chip cell came with it
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == NAME] == [CELL]
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
    assert names[at - 1] == "laguna_head_loss_ms_per_step"    # appended
    # no entry that was there lists the new cell
    for m in manifest["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", [])


# -- the pair and operation counts ---------------------------------------------------


def test_the_counts_are_pinned(config):
    ref = reference()
    assert ref.selected_pairs(2048, 8192) == 14681088
    assert ref.causal_pairs(8192) == 33558528
    assert ref.selected_pairs(2048, 2048) == 2048 * 2049 // 2
    assert ref.selected_pairs(2048, 100) == 100 * 101 // 2
    # forward, all eight layers, one row: 240.5 and 68.7 GFLOP a layer
    assert ref.attention_flops(config, 8192) == 1924279566336.0
    assert ref.index_flops(config, 8192) == 549822922752.0
    assert ref.matrix_params_per_position(config) == 228950016.0
    view = {"config": config, "rows": 1}
    assert ref.train_step_flops(view) == 18675658653696.0 \
        == 6 * 8192 * 228950016.0 + 3 * (1924279566336.0 + 549822922752.0)
    # the held experts count by the EXPECTED pairs: 8 x 8 / 128 a position
    half = dict(config, num_experts=4)
    assert ref.matrix_params_per_position(config) \
        - ref.matrix_params_per_position(half) \
        == 8 * 4 * 3 * 2048 * 768 * (8 * 4 / 128) / 4
    assert ref.expert_pair_flops(config) == 2.0 * 3 * 2048 * 768
    # 18.7 TFLOP a step; attention and the indexer's scores two fifths
    share = 3 * (1924279566336.0 + 549822922752.0) / 18675658653696.0
    assert 0.39 < share < 0.41


def test_the_parameters_are_the_tables(config):
    """551.0 M by the issue's table; the net built from the conf's layer
    lines holds the same (shapes only: nothing is allocated)."""
    E, V = config["hidden_size"], config["vocab_size"]
    attn = 2 * E * 32 * 128 + 2 * E * 4 * 128 + 2 * 128
    indexer = E * 16 * 64 + E * 64 + E * 16 + 2 * 64
    layer = attn + indexer + E * 128 + 8 * 3 * E * 768 + 2 * E
    assert 8 * layer + 2 * V * E + E == 550999040
    import jax
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.graph import build_graph
    from cxxnet_tpu.model import Network
    with open(os.path.join(BENCH, "configs", NAME + ".conf")) as f:
        cfg = parse_config_string(f.read() + "batch_size = 1\n")
    net = Network(build_graph(cfg), cfg)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) == 550999040


# -- check() against the program, sound and with each fault planted ---------------


@pytest.fixture(scope="module")
def trained():
    """The toy configuration through the program's own update path: six
    steps on one staged batch, as the harness's warm-up makes them."""
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    with open(os.path.join(TOY, "configs", "keye_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "keye_toy.conf")) as f:
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
            "probe_loss_metric_abs_diff", "probe_index_loss_rel_diff",
            "grad_norm_embed_rel_diff", "grad_norm_head_rel_diff",
            "grad_norm_routers_rel_diff", "grad_norm_indexer_rel_diff",
            "grad_norm_b0_rel_diff", "grad_norm_b1_rel_diff",
            "moe_pairs_dropped", "selected_pairs_worst_layer_abs_diff",
            "select_pairs_abs_diff", "select_share_short_of_one_diff",
            "rotary_grid_max_abs_diff"} <= set(compared) | {
                "moe_pairs_dropped"}
    assert all(k + "_limit" in said for k in compared)
    assert said["moe_pairs_dropped"] == 0.0
    # cross-entropy and the indexer's loss are told apart
    assert said["probe_index_loss_reference"] > 0
    assert said["probe_loss_reference"] > said["probe_index_loss_reference"]
    assert said["grad_norm_indexer_worst_leaf"].split("/")[1] in (
        "iq", "ik", "iknorm", "iw")
    assert said["select_pairs_program"] == said["selected_pairs_expected"] \
        == 2 * (8 * 9 // 2 + 24 * 8)
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
    # where the fault shows: the gradients' routes are told apart
    if variant == "no_index_loss":
        assert set(over) <= {
            "loss_step1_abs_diff", "loss_step2_abs_diff",
            "loss_step3_abs_diff", "probe_loss_abs_diff",
            "probe_index_loss_rel_diff", "grad_norm_indexer_rel_diff"}
        assert "grad_norm_indexer_rel_diff" in over
    if variant in ("index_attached", "p_attached"):
        assert "grad_norm_b0_rel_diff" in over
        assert "grad_norm_indexer_rel_diff" not in over
        assert "probe_loss_abs_diff" not in over
    if variant == "sections_permuted":
        assert over == ["rotary_grid_max_abs_diff"]    # the cell is text
    if variant in ("topk_half", "no_relu", "no_w"):
        assert "select_share_short_of_one_diff" in over


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", NAME + ".py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(cxxnet_tpu|benchmarks)",
                         text, re.M)
    assert "joyai_llm_flash" not in text and "laguna" not in text


# -- the cell, walked by the rehearsal ---------------------------------------------


def test_the_toy_cell_walks_the_harness():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(TOY, "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", "keye_toy_resident", "--seed", "3000000019",
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
    assert result["compared"]["select_share_in_reference"] >= 0.98
    picked = [line for line in lines if "fused_kernels" in line][0]
    assert picked["fused_kernels"]["grouped"] == {"ragged_dot": 2}
    assert picked["fused_kernels"]["attention"] == {"gqa.ref_sparse": 2}
    steps = [line for line in lines if "items" in line][0]
    assert steps["items"] == steps["steps"] * 2 * 32      # positions


def test_a_control_cell_reads_not_correct_through_the_harness():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(TOY, "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", "keye_toy_select_off_resident", "--seed", "77",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1]["correct"] is False, proc.stderr[-2000:]
    assert lines[-1]["compared"]["variant"] == "select_off"


def test_the_toy_manifest_names_every_control():
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        toy = json.load(f)
    assert [c["name"] for c in toy["configs"]] == ["keye_toy"] + [
        "keye_toy_" + v for v in CONTROLS]
    for c in toy["configs"][1:]:
        with open(os.path.join(TOY, c["file"])) as f:
            held = json.load(f)
        assert held["reference"] == NAME + "_" + c["name"][9:]
        assert os.path.isfile(os.path.join(
            DATA, "keye_controls", "references", held["reference"] + ".py"))


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
        "custom-call.4": "jit(one)/jvp(b0_attn)/gqa.attend.sparse/"
                         "pallas_call",
        "custom-call.5": "jit(one)/transpose(jvp(b1_attn))/"
                         "gqa.attend.sparse/pallas_call",
        "fusion.6": "jit(one)/jvp(b0_attn)/gqa.select/while/reduce_sum",
        "custom-call.7": "jit(one)/jvp(b0_attn)/gqa.index/pallas_call",
        "fusion.8": "jit(one)/transpose(jvp(b0_attn))/gqa.index/"
                    "dot_general",
        "fusion.9": "jit(one)/jvp(b0_attn)/gqa.proj/dot_general",
        "fusion.10": "jit(one)/jvp(b0_attn)/gqa.index_loss/reduce_sum",
    }
    monkeypatch.setattr(program_reads, "_program", lambda: (table, classify))
    monkeypatch.setattr(joyai_reads, "_program", lambda: (table, classify))
    counters = {"cxxnet_moe_pairs_held_last_step": 8 * 4096.0}
    monkeypatch.setattr(joyai_reads, "counter", counters.get)
    monkeypatch.setattr(joyai_reads, "gauge_max", lambda name: 1.5)
    by_name = {"fusion %fusion.1": 0.002, "fusion %fusion.2": 0.006,
               "fusion %fusion.3": 0.004, "custom-call %custom-call.4": 0.1,
               "custom-call %custom-call.5": 0.3, "fusion %fusion.6": 0.24,
               "custom-call %custom-call.7": 0.05, "fusion %fusion.8": 0.35,
               "fusion %fusion.9": 0.5, "fusion %fusion.10": 0.05}
    return {"trace": {"devices": [{"by_name": by_name, "steps": 2}]},
            "rows": 1, "chips": 1, "peaks": {"bf16_tflops": 197.0},
            "spans": [], "span_window_s": 0.0, "step_flops": 1.0,
            "compiles_in_window": 0}


def reader(name):
    return load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                "bench_reader_" + name)


def test_the_time_readers(view):
    want = {"dsa_index_ms_per_step": 200.0,
            "dsa_select_ms_per_step": 120.0,
            "dsa_attend_ms_per_step": 200.0,
            "keye_moe_route_ms_per_step": 1.0,
            "keye_moe_experts_ms_per_step": 5.0}
    for name, ms in want.items():
        assert reader(name).read(view) == pytest.approx(ms), name
    assert reader("keye_moe_load_max_over_mean").read(view) == 1.5


def test_the_roofline_readers(view, config):
    ref = reference()
    want = 3 * ref.attention_flops(config, 8192) / 0.2 / 197e12
    assert reader("dsa_attend_roofline_pct").read(view) \
        == pytest.approx(100 * want)
    assert 0 < 100 * want < 100
    want = 3 * ref.index_flops(config, 8192) / 0.2 / 197e12
    assert reader("dsa_index_roofline_pct").read(view) \
        == pytest.approx(100 * want)
    assert 0 < 100 * want < 100
    want = 3 * 8 * 4096 * ref.expert_pair_flops(config) / 5e-3 / 197e12
    assert reader("keye_moe_experts_roofline_pct").read(view) \
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
    for name in set(NEW_METRICS) - {"keye_moe_load_max_over_mean"}:
        assert reader(name).read(dict(view, trace=None)) is None


def test_the_counters_come_with_the_train_metric(trained):
    """The ``cxxnet_moe_*`` family is fed by this configuration's expert
    layers, and the sparse layers' own gauges by every drained step."""
    from benchmarks.joyai_reads import _family, counter
    tr = trained["trainer"]
    before = counter("cxxnet_moe_steps_total") or 0
    dsa0 = counter("cxxnet_dsa_steps_total") or 0
    held0 = counter("cxxnet_moe_pairs_held_total") or 0
    away0 = counter("cxxnet_moe_pairs_elsewhere_total") or 0
    tr.update(trained["batch0"])
    tr.update(trained["batch0"])
    tr.train_metric_report()
    steps = counter("cxxnet_moe_steps_total") - before
    assert steps >= 2 and counter("cxxnet_dsa_steps_total") - dsa0 == steps
    held = counter("cxxnet_moe_pairs_held_total") - held0
    away = counter("cxxnet_moe_pairs_elsewhere_total") - away0
    # two expert layers, 2 x 32 positions, 3 experts a position
    assert held + away == steps * 2 * 2 * 32 * 3
    assert held > 0 and away > 0
    assert counter("cxxnet_moe_pairs_dropped_total") == 0

    def by_layer(name):
        return {labels[0]: child.value
                for labels, child in _family(name).samples()}
    pairs = by_layer("cxxnet_dsa_selected_pairs")
    assert pairs["b0_attn"] == pairs["b1_attn"] == 2 * (36 + 24 * 8)
    assert by_layer("cxxnet_dsa_index_loss")["b1_attn"] > 0
    # 32 positions are one tile: executed and total are both 1
    assert by_layer("cxxnet_attn_tiles_executed")["b0_attn"] == 1
    assert by_layer("cxxnet_attn_tiles_total")["b0_attn"] == 1
