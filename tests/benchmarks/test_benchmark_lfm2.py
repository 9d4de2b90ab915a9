"""The ``lfm2_8b_a1b`` configuration in the benchmark: its file and
entries, its reference module's ``check`` against the program at the toy
size — sound, and with each control's fault planted, which has to come
out not correct — its pinned operation counts, its cell walked by the CPU
rehearsal, and its three readers on the view a traced chip run handed
them (recorded) and on a hand-made one. On the CPU backend at a toy size:
no number here is a device number. Entries are found by name, not by
place: a later configuration goes after them."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(ROOT, "tests", "benchmarks", "data")
TOY = os.path.join(DATA, "lfm2_toy")
NAME = "lfm2_8b_a1b"
CONTROLS = ["float8", "conv_ahead", "no_bias"]
CELL = "lfm2_ep4_train_8k"
NEW_METRICS = ["lfm2_shortconv_ms_per_step",
               "lfm2_shortconv_mix_ms_per_step",
               "lfm2_shortconv_roofline_pct"]
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
#: the catalog's row (its ``config``), as published
PUBLISHED_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "full_attention",
                   "conv", "conv"]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(variant=None):
    if variant is None:
        return load(os.path.join(BENCH, "references", NAME + ".py"),
                    "bench_lfm2_ref")
    return load(os.path.join(DATA, "lfm2_controls", "references",
                             f"{NAME}_{variant}.py"),
                "bench_lfm2_ref_" + variant)


def conf_tool():
    return load(os.path.join(ROOT, "tools", "gen_joyai_conf.py"),
                "gen_conf_for_lfm2_tests")


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
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert config[key] == value, key
    # the cut, each beside what was published, and the deployment
    assert config["reduced"] == REDUCED
    assert config["published"] == {
        "num_hidden_layers": 24, "num_dense_layers": 2,
        "layer_types": PUBLISHED_TYPES, "num_experts": 32,
        "vocab_size": 65536}
    assert [config[k] for k in REDUCED] == [
        5, 1, ["conv", "full_attention", "conv", "conv", "conv"], 8, 16384]
    # the leading dense layer once, then one whole period of the published
    # pattern: published layers 2..5
    assert config["layer_types"] == [PUBLISHED_TYPES[0]] \
        + PUBLISHED_TYPES[2:6]
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    # the floors: at least four layers after the dense one, 8 experts in a
    # range that is not the first, an eighth of the vocabulary or more
    assert config["num_hidden_layers"] - config["num_dense_layers"] >= 4
    assert config["num_experts_published"] == 32
    assert config["vocab_size"] * 4 == 65536
    first = config["expert_first"]
    assert first == 8 and first + config["num_experts"] <= 32
    assert config["hidden_size"] // config["num_attention_heads"] == 64
    for said in ("one chip of 4", "experts 8..15", "16384 of 65536",
                 "partial sum", "19 of 24"):
        assert said in config["deployment"], said
    for said in ("num_hidden_layers 5", "not tied", "bias_update_rate "
                 "0.01:", "1e-20", "1e-6", "intermediate_size 7168",
                 "B, C, x", "head_dim 64", "eta 0.0001", "init_sigma",
                 "1 row of 8192", "remat = 1"):
        assert any(said in a for a in config["assumed"]), said
    assert "overrides" not in config


def test_the_conf_is_the_generators_output(config):
    tool = conf_tool()
    with open(os.path.join(BENCH, "configs", NAME + ".conf")) as f:
        text = f.read()
    assert text == tool.conf(config)
    assert text.count("= shortconv:") == 4 and text.count("= gqa:") == 1
    assert text.count("= ffn:") == 1 and text.count("= moe:") == 4
    assert text.count("conv_L_cache = 3") == 4
    assert text.count("head_dim = 64") == 1
    assert text.count("router = sigmoid") == 4
    assert text.count("shared_expert = 0") == 4
    assert text.count("expert_first = 8") == 4
    assert text.count("bias_update_rate = 0.01\n") == 4
    assert text.count("nhidden = 7168") == 1
    with open(os.path.join(TOY, "configs", "lfm2_toy.json")) as f:
        toy = json.load(f)
    with open(os.path.join(TOY, "configs", "lfm2_toy.conf")) as f:
        assert f.read() == tool.conf(toy)


@pytest.mark.parametrize("stem", [
    os.path.join(BENCH, "configs", "joyai_llm_flash"),
    os.path.join(BENCH, "configs", "laguna_s_2_1"),
    os.path.join(BENCH, "configs", "keye_vl_2_0_30b_a3b"),
    os.path.join(DATA, "keye_toy", "configs", "keye_toy")])
def test_the_conf_tool_writes_the_other_families_as_before(stem):
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
    names = [c["name"] for c in manifest["configs"]]
    assert names[names.index(NAME) - 1] == "keye_vl_2_0_30b_a3b"  # appended


def test_the_entries(manifest):
    cell = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(cell) == 1
    cell = cell[0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "resident_tokens_8k", 1)
    assert len(cell["why"]) <= 200
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[cells.index(CELL) - 1] == "keye_ep16_train_8k"  # appended
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == NAME] == [CELL]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_items_per_s_chip"
        assert by_name[name]["layer"] == "kernels"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["unit"] == (
            "%" if name.endswith("_roofline_pct") else "ms")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 3] == NEW_METRICS
    assert names[at - 1] == "setup_input_s"                     # appended
    for m in manifest["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", [])


# -- the operation counts ---------------------------------------------------


def test_the_counts_are_pinned(config):
    ref = reference()
    E = 2048
    # 2 (4 E^2 + L E) a position a layer, four layers, forward
    assert ref.shortconv_flops(config, 8192) \
        == 2.0 * 8192 * (4 * E * E + 3 * E) * 4 == 1099914280960.0
    assert ref.attention_flops(config, 8192) \
        == 2.0 * (8192 * 8193 / 2) * 2 * 64 * 32 == 274911461376.0
    assert ref.expert_pair_flops(config) == 2.0 * 3 * E * 1792
    # head, four convolutions, one attention layer's projections, the
    # dense layer, four routers and the held experts by the expected pairs
    # (4 x 8 / 32 = 1 a position)
    assert ref.matrix_params_per_position(config) == (
        E * 16384 + 4 * (4 * E * E + 3 * E) + (2 * E * E + 2 * E * 512)
        + 3 * E * 7168 + 4 * (E * 32 + 3 * E * 1792)) == 199516160.0
    view = {"config": config, "rows": 1}
    flops = ref.train_step_flops(view)
    assert flops == 6 * 8192 * 199516160.0 + 3 * 274911461376.0 \
        == 10631352680448.0
    # the forward count of the cut (3.54 TFLOP, PERF.md section 4) and
    # its shares
    forward = flops / 3
    assert 3.53e12 < forward < 3.55e12
    assert 0.30 < ref.shortconv_flops(config, 8192) / forward < 0.32
    assert 0.075 < ref.attention_flops(config, 8192) / forward < 0.08


# -- check() against the program, sound and with each fault planted ---------------


@pytest.fixture(scope="module")
def trained():
    """The toy configuration through the program's own update path: six
    steps on one staged batch, as the harness's warm-up makes them."""
    import numpy as np
    from cxxnet_tpu.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.trainer import Trainer
    with open(os.path.join(TOY, "configs", "lfm2_toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TOY, "configs", "lfm2_toy.conf")) as f:
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
    compared = [k for k in said if k.endswith("_diff")]
    assert {"loss_step1_abs_diff", "loss_step2_abs_diff",
            "loss_step3_abs_diff", "probe_loss_abs_diff",
            "probe_loss_metric_abs_diff", "grad_norm_embed_rel_diff",
            "grad_norm_head_rel_diff", "grad_norm_routers_rel_diff",
            "grad_norm_conv_taps_rel_diff", "grad_norm_conv_in_rel_diff",
            "grad_norm_b0_rel_diff", "grad_norm_b1_rel_diff",
            "grad_norm_b2_rel_diff"} <= set(compared)
    assert all(k + "_limit" in said for k in compared)
    assert said["moe_pairs_dropped"] == 0.0
    assert said["grad_norm_conv_taps_worst_leaf"].endswith("_conv/conv/wmat")
    assert said["grad_norm_conv_in_worst_leaf"].endswith(
        "_conv/in_proj/wmat")
    assert trained["warm_losses"][-1] < trained["warm_losses"][0]
    # the probe was one more step of the trainer's own update, and the
    # routers have their own bias back
    tr = trained["trainer"]
    assert int(tr.opt_state["t"]) >= 7
    import numpy as np
    steps = np.asarray(tr.net_state["b1_moe"]["sel_bias"]) \
        / trained["config"]["bias_update_rate"]
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)


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
    if variant == "conv_ahead":
        assert "grad_norm_conv_taps_rel_diff" in over
    if variant == "no_bias":
        # the three steps start from a zero bias: the planted one shows
        assert "probe_loss_abs_diff" in over
        assert "grad_norm_routers_rel_diff" in over


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", NAME + ".py")) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(cxxnet_tpu|benchmarks)",
                         text, re.M)
    for other in ("joyai_llm_flash", "laguna", "keye"):
        assert other not in text


# -- the cell, walked by the rehearsal ---------------------------------------------


def _rehearse(workload, seed, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(TOY, "BENCHMARK.json"), "--rehearse-cpu",
         "--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    return proc, lines


def test_the_toy_cell_walks_the_harness():
    proc, lines = _rehearse("lfm2_toy_resident", 3000000019, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["compared"]["moe_pairs_dropped"] == 0.0
    picked = [line for line in lines if "fused_kernels" in line][0]
    assert picked["fused_kernels"] == {"attention": {"gqa.ref": 1},
                                       "grouped": {"ragged_dot": 2}}
    steps = [line for line in lines if "items" in line][0]
    assert steps["items"] == steps["steps"] * 2 * 32      # positions


def test_a_control_cell_reads_not_correct_through_the_harness():
    proc, lines = _rehearse("lfm2_toy_conv_ahead_resident", 77, 0)
    assert lines[-1]["correct"] is False, proc.stderr[-2000:]
    assert lines[-1]["compared"]["variant"] == "conv_ahead"


def test_the_toy_manifest_names_every_control():
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        toy = json.load(f)
    assert [c["name"] for c in toy["configs"]] == ["lfm2_toy"] + [
        "lfm2_toy_" + v for v in CONTROLS]
    for c in toy["configs"][1:]:
        with open(os.path.join(TOY, c["file"])) as f:
            held = json.load(f)
        assert held["reference"] == NAME + "_" + c["name"][9:]
        assert os.path.isfile(os.path.join(
            DATA, "lfm2_controls", "references", held["reference"] + ".py"))


# -- the readers -----------------------------------------------------------------


def reader(name):
    return load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                "bench_reader_" + name)


def _view(by_name, steps, table, monkeypatch):
    from benchmarks import joyai_reads
    from cxxnet_tpu.telemetry.traceparse import classify
    monkeypatch.setattr(joyai_reads, "_program", lambda: (table, classify))
    return {"trace": {"devices": [{"by_name": by_name, "steps": steps}]},
            "rows": 1, "chips": 1, "peaks": {"bf16_tflops": 197.0},
            "spans": [], "span_window_s": 0.0, "step_flops": 1.0,
            "compiles_in_window": 0}


@pytest.fixture()
def view(monkeypatch):
    """Two steps of a device trace with instructions under each
    sub-scope, forward, rebuilt and backward, and some of no short
    convolution: a ``shortconvx`` scope is another layer's."""
    table = {
        "fusion.1": "jit(one)/jvp(b0_conv)/shortconv.proj/dot_general",
        "fusion.2": "jit(one)/jvp(b0_conv)/shortconv.mix/mul",
        "fusion.3": "jit(one)/transpose(jvp(b2_conv))/shortconv.mix/pad",
        "fusion.4": "jit(one)/jvp(b2_conv)/checkpoint/shortconv.proj/"
                    "dot_general",
        "fusion.5": "jit(one)/transpose(jvp(b2_conv))/shortconv.proj/"
                    "dot_general",
        "fusion.6": "jit(one)/jvp(b1_attn)/gqa.proj/dot_general",
        "fusion.7": "jit(one)/jvp(b1_moe)/moe.route/top_k",
        "fusion.8": "jit(one)/jvp(x)/shortconvx.mix/mul",
    }
    by_name = {"fusion %fusion.1": 0.02, "fusion %fusion.2": 0.004,
               "fusion %fusion.3": 0.006, "fusion %fusion.4": 0.03,
               "fusion %fusion.5": 0.04, "fusion %fusion.6": 0.5,
               "fusion %fusion.7": 0.1, "fusion %fusion.8": 0.3}
    return _view(by_name, 2, table, monkeypatch)


def test_the_readers_on_a_hand_made_view(view, config):
    assert reader("lfm2_shortconv_ms_per_step").read(view) \
        == pytest.approx(50.0)
    assert reader("lfm2_shortconv_mix_ms_per_step").read(view) \
        == pytest.approx(5.0)
    want = 3 * reference().shortconv_flops(config, 8192) / 0.05 / 197e12
    assert reader("lfm2_shortconv_roofline_pct").read(view) \
        == pytest.approx(100 * want)


def test_the_readers_on_a_recorded_trace(monkeypatch, config):
    """What the readers were handed in a traced run of the cell on a TPU
    v5e (``lfm2_controls/record_view.py``; PERF.md section 6):
    every instruction under a ``shortconv`` scope, by the compiled step's
    own scope table, and the slowest of the rest. The projections'
    products carry ``shortconv.proj``: the roofline share is of their
    time and the mix's, and under 100."""
    with open(os.path.join(DATA, "lfm2_trace_view.json")) as f:
        rec = json.load(f)
    view = _view(rec["by_name"], rec["steps"], rec["scope_table"],
                 monkeypatch)
    from cxxnet_tpu.telemetry.traceparse import scope_path
    scopes = {k: scope_path(rec["scope_table"][k.rsplit(" ", 1)[-1]
                                               .lstrip("%")] or "")[1]
              for k in rec["by_name"]}
    mine = [k for k, s in scopes.items()
            if any(p.startswith("shortconv.") for p in s)]
    mix = [k for k in mine if "shortconv.mix" in scopes[k]]
    total = sum(rec["by_name"][k] for k in mine)
    ms = reader("lfm2_shortconv_ms_per_step").read(view)
    assert ms == pytest.approx(1e3 * total / rec["steps"])
    assert reader("lfm2_shortconv_mix_ms_per_step").read(view) \
        == pytest.approx(1e3 * sum(rec["by_name"][k] for k in mix)
                         / rec["steps"])
    pct = reader("lfm2_shortconv_roofline_pct").read(view)
    assert pct == pytest.approx(
        100 * 3 * reference().shortconv_flops(config, 8192)
        / (total / rec["steps"]) / 197e12)
    assert 0 < pct < 100
    # the projections' products are in the scope, on the MXU: the
    # convolution fusions among the kind's instructions
    assert any(k.startswith("convolution") or "fusion" in k for k in mine)
    assert any("shortconv.proj" in scopes[k] for k in mine)
    assert len(rec["by_name"]) > len(mine)      # and the rest is not read


def test_the_readers_find_nothing_in_a_program_without_the_scopes(
        view, monkeypatch):
    from benchmarks import joyai_reads
    monkeypatch.setattr(joyai_reads, "_program", lambda: ({}, None))
    for name in NEW_METRICS:
        assert reader(name).read(view) is None, name
    monkeypatch.setattr(joyai_reads, "_program", lambda: None)
    for name in NEW_METRICS:
        assert reader(name).read(view) is None, name
    for name in NEW_METRICS:
        assert reader(name).read(dict(view, trace=None)) is None
