"""What a configuration brings to ``benchmarks/run.py`` as files (PR 27):
its plain reference with ``check`` and ``train_step_flops``, its
``items_per_row``, a token feed. The toy sequence model's reference
against the program on the CPU and its count against a hand count; a
cell made only of new files, whose rows are positions; what the harness
refuses; a planted fault that has to come out as not correct.

Each harness case is one run of the command in a process of its own, on
the CPU backend at a toy size: no number here is a device number.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cxxnet_tpu.config import parse_config_string  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.trainer import Trainer  # noqa: E402

RUN = os.path.join(ROOT, "benchmarks", "run.py")
TOY = os.path.join(ROOT, "tests", "benchmarks", "data", "toy")
ROWS, POSITIONS, VOCAB = 8, 64, 32


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


toy_lm = load(os.path.join(TOY, "references", "toy_lm.py"), "bench_toy_lm")


def run(manifest, cell, trace=0, seed=3000000019):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, RUN, "--manifest", str(manifest), "--rehearse-cpu",
         "--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


def lines_of(proc):
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]


def no_result(proc):
    """A non-zero exit whose standard output holds no result line."""
    assert proc.returncode != 0
    assert not [line for line in lines_of(proc) if "correct" in line]
    return proc.stderr


def toy_copy(tmp_path, **config_edits):
    """The toy manifest in a directory of the test's own, with
    ``toy_lm``'s configuration file edited."""
    work = tmp_path / "toy"
    shutil.copytree(TOY, work)
    path = work / "configs" / "toy_lm.json"
    held = json.loads(path.read_text())
    for key, value in config_edits.items():
        if value is None:
            held.pop(key)
        else:
            held[key] = value
    path.write_text(json.dumps(held))
    return work


# -- the toy reference against the program ----------------------------------


def build(seed=5, **edits):
    with open(os.path.join(TOY, "configs", "toy_lm.conf")) as f:
        text = f.read()
    for old, new in edits.items():
        assert old in text
        text = text.replace(old, new)
    tr = Trainer(parse_config_string(
        text + f"dev = cpu:0\nseed = {seed}\nbatch_size = {ROWS}\n"))
    tr.init_model()
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, VOCAB, (ROWS, POSITIONS))
    label = (toks + toks[:, :1]) % VOCAB
    batch = DataBatch(
        data=toks.astype(np.float32).reshape(ROWS, 1, 1, POSITIONS),
        label=label.astype(np.float32))
    return tr, batch, toks.astype(np.int32), label.astype(np.int32)


def reference_view(tr, batch, params0, loss0):
    return {"layers": tr.graph.layers, "defaults": dict(tr.graph.defcfg),
            "trainer": tr, "params0": params0, "batch0": batch,
            "warm_losses": [loss0], "dtype": "float32", "rows": ROWS,
            "chips": 1, "config": {}, "say": print}


@pytest.mark.parametrize("edits", [
    {}, {"causal = 1": "causal = 0"},
    {"layer[+1:f1] = ffn:ffn1": "layer[+1:f1] = ffn:ffn1\n  act = relu"},
], ids=["causal_gelu", "not_causal", "relu"])
def test_toy_lm_train_loss_matches_the_program(edits):
    import jax
    tr, batch, toks, label = build(**edits)
    assert {s.type for s in tr.graph.layers} == {
        "embed", "layernorm", "mha", "ffn", "add", "seqfc", "lmloss"}
    params0 = jax.tree_util.tree_map(np.array, tr.params)
    tr.update(batch)
    ok, said = toy_lm.check("train_loss", reference_view(
        tr, batch, params0, tr.last_loss))
    assert ok and list(said) == ["check", "program", "reference",
                                 "abs_diff", "tolerance"]
    assert said["abs_diff"] < 2e-6 < said["tolerance"]
    # teeth: the mask dropped, or a weight read in the wrong layout,
    # moves the reference by far more than the limit
    loss = jax.jit(toy_lm.make_loss_fn(tr.graph.layers,
                                       dict(tr.graph.defcfg)))
    broken = dict(params0)
    broken["lm_head"] = {"wmat": np.asarray(params0["lm_head"]["wmat"])[::-1],
                         "bias": params0["lm_head"]["bias"]}
    assert abs(float(loss(broken, toks, label)) - said["reference"]) \
        > 100 * said["tolerance"]
    with pytest.raises(ValueError, match="no check 'eval_logits'"):
        toy_lm.check("eval_logits", {})


def test_toy_lm_unknown_layer_kind_is_an_error():
    tr, batch, toks, _ = build()
    import dataclasses
    layers = list(tr.graph.layers)
    layers[1] = dataclasses.replace(layers[1], type="rmsnorm")
    with pytest.raises(ValueError, match="no layer kind 'rmsnorm'"):
        toy_lm.forward(layers, {}, tr.params, toks)


def test_toy_lm_count_against_a_hand_count():
    """E 32, 4 heads, feed-forward 64, vocabulary 32, 64 positions: two
    operations a multiply-add of the four attention projections, the two
    attention products over the causal pairs, the feed-forward's two
    products and the head; three times that for a step."""
    tr, batch, _, _ = build()
    s, e, f, v = POSITIONS, 32, 64, VOCAB
    pairs = s * (s + 1) // 2
    forward = (4 * 2 * s * e * e + 2 * 2 * pairs * e
               + 2 * 2 * s * e * f + 2 * s * e * v)
    layers, defaults = tr.graph.layers, dict(tr.graph.defcfg)
    assert toy_lm.forward_flops_per_row(layers, defaults, s) == forward
    view = reference_view(tr, batch, None, 0.0)
    assert toy_lm.train_step_flops(view) == 3 * ROWS * forward
    # not causal: every pair of positions
    tr2, batch2, _, _ = build(**{"causal = 1": "causal = 0"})
    assert toy_lm.forward_flops_per_row(
        tr2.graph.layers, dict(tr2.graph.defcfg), s) \
        == forward + 2 * 2 * (s * s - pairs) * e


# -- the harness -------------------------------------------------------------


NEW_FEED = '''
"""A token feed a later PR brings: rows of positions of the program's
synthetic_lm iterator under a task of the mix's choosing, staged once
as the resident feed stages its batch."""
from benchmarks.feeds import resident


def section(traffic, ctx):
    return ("data = train\\niter = synthetic_lm\\n"
            f"  num_inst = {ctx['rows']}\\n  batch_size = {ctx['rows']}\\n"
            f"  vocab_size = {ctx['config']['vocab_size']}\\n"
            f"  seq_len = {ctx['config']['positions']}\\n"
            f"  lm_task = {traffic['lm_task']}\\n"
            f"  seed_data = {ctx['seed']}\\niter = end\\n")


def open(task, tr, traffic, ctx):
    assert "num_class" not in ctx       # the file has none
    ctx["say"](feed="new_tokens", lm_task=traffic["lm_task"])
    return resident.open(task, tr, traffic, ctx)
'''

NEW_METRIC = '''
"""A per-layer metric a later PR brings: operations a position of a step
takes, by the configuration's own count."""


def read(view):
    return view["step_flops"] / view["rows"] / 64
'''


@pytest.mark.parametrize("trace", [0, 1])
def test_a_sequence_cell_made_only_of_new_files_runs(tmp_path, trace):
    """The add-files-only property for what PR 27 made a file: a new
    configuration with its conf, a reference module (check and count)
    under a name of its own, ``items_per_row`` over 1, a token mix and
    feed and a per-layer reader, in a directory of their own with their
    own manifest entries, run without an edit to benchmarks/ — and the
    run counts positions."""
    before = {
        os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(os.path.join(ROOT, "benchmarks"))
        if ".cache" not in d and "__pycache__" not in d for f in fs}
    for sub in ("configs", "traffic", "feeds", "layer_metrics",
                "references"):
        os.makedirs(tmp_path / sub)
    shutil.copy(os.path.join(TOY, "configs", "toy_lm.conf"),
                tmp_path / "configs" / "newlm.conf")
    shutil.copy(os.path.join(TOY, "references", "toy_lm.py"),
                tmp_path / "references" / "new_lm.py")
    (tmp_path / "configs" / "newlm.json").write_text(json.dumps({
        "name": "newlm", "source": "a test", "net": {"conf": "newlm.conf"},
        "overrides": ["eta = 0.002"], "input_shape": [1, 1, POSITIONS],
        "vocab_size": VOCAB, "positions": POSITIONS,
        "compute_dtype": "float32", "item": "token",
        "items_per_row": POSITIONS, "reference": "new_lm",
        "check": "train_loss", "reduced": [], "assumed": []}))
    (tmp_path / "traffic" / "new_tokens.json").write_text(json.dumps({
        "name": "new_tokens", "feed": "new_tokens", "rows_per_chip": 4,
        "lm_task": "copy"}))
    (tmp_path / "feeds" / "new_tokens.py").write_text(NEW_FEED)
    (tmp_path / "layer_metrics" / "flop_per_position.py").write_text(
        NEW_METRIC)
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "newlm", "source": "a test",
                            "file": "configs/newlm.json", "reduced": [],
                            "why": "add-files-only"}]
    manifest["workloads"] = [{"name": "new_cell", "config": "newlm",
                              "traffic": "new_tokens", "chips": 1,
                              "why": "add-files-only"}]
    manifest["per_layer"].append({
        "name": "flop_per_position", "unit": "flop", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "train_items_per_s_chip", "workloads": ["new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    proc = run(tmp_path / "BENCHMARK.json", "new_cell", trace, seed=7)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = lines_of(proc)
    out = said[-1]
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert {"feed": "new_tokens", "lm_task": "copy"} in said
    # the reference module's own line, as the convnets' is printed
    (checked,) = [line for line in said if line.get("check")]
    assert checked["ok"] is True and checked["abs_diff"] \
        <= checked["tolerance"] == toy_lm.LOSS_TOL["float32"]
    assert list(out)[-1] == "compared"
    assert out["compared"]["abs_diff"] == checked["abs_diff"]
    assert proc.stderr.strip().splitlines()[-1] \
        == "compared: " + json.dumps(out["compared"])
    # rows of positions: 4 rows x 64 positions a step
    (counted,) = [line for line in said if "items" in line]
    assert counted["steps"] == out["attempted"] > 0
    assert counted["items"] == 4 * POSITIONS * counted["steps"]
    if trace:
        tr, batch, _, _ = build()
        per_position = 3 * toy_lm.forward_flops_per_row(
            tr.graph.layers, dict(tr.graph.defcfg), POSITIONS) / POSITIONS
        assert out["metrics"]["flop_per_position"]["value"] == per_position
        assert "dispatch_ms_per_step" in out["metrics"]  # an old reader too
        assert "step_mfu_pct" not in out["metrics"]      # no trace: left out
    else:
        rate = out["metrics"]["train_items_per_s_chip"]["value"]
        assert rate == pytest.approx(
            counted["items"] / counted["window_s"], rel=1e-9)
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before


@pytest.mark.parametrize("edit,held", [
    ({"items_per_row": 32}, "items_per_row 32"),
    ({"items_per_row": None}, "items_per_row 1"),
    ({"item": "image"}, "items_per_row 64"),
], ids=["half", "left_out", "an_image_of_64"])
def test_a_wrong_items_per_row_exits_nonzero_with_no_result(tmp_path, edit,
                                                            held):
    """A row of the first batch holds 64 positions, and an image is one
    item: a configuration file that says otherwise is stopped before the
    window, whatever it would have counted."""
    work = toy_copy(tmp_path, **edit)
    err = no_result(run(work / "BENCHMARK.json", "toy_lm_resident"))
    assert held in err and "a row of the first batch holds" in err


@pytest.mark.parametrize("missing", ["check", "train_step_flops"])
def test_a_reference_module_that_lacks_a_function_is_named(tmp_path,
                                                           missing):
    work = toy_copy(tmp_path, reference="half_a_reference")
    with open(work / "references" / "toy_lm.py") as f:
        text = f.read()
    assert f"\ndef {missing}(" in text
    (work / "references" / "half_a_reference.py").write_text(
        text.replace(f"\ndef {missing}(", f"\ndef _{missing}("))
    err = no_result(run(work / "BENCHMARK.json", "toy_lm_resident"))
    assert f"half_a_reference.py has no {missing}()" in err
    # and a reference that no directory holds
    work2 = toy_copy(tmp_path / "second", reference="nowhere")
    assert "no references/nowhere.py under any of" in no_result(
        run(work2 / "BENCHMARK.json", "toy_lm_resident"))


def test_a_planted_fault_in_the_reference_reads_not_correct(tmp_path):
    """``toy_lm_weights_off`` hands the reference weights off by 1 %:
    the run ends with code 0 and ``"correct": false``, the number that
    failed beside its limit."""
    work = toy_copy(tmp_path, reference="toy_lm_weights_off")
    proc = run(work / "BENCHMARK.json", "toy_lm_resident")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = lines_of(proc)[-1]
    assert out["correct"] is False
    assert out["failed"] == 0 and out["attempted"] > 0
    c = out["compared"]
    assert c["abs_diff"] > 2 * c["tolerance"]
    assert c["warmup_loss_last"] < c["warmup_loss_first"]
    assert "compared: " in proc.stderr.strip().splitlines()[-1]
