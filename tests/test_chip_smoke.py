"""chip_smoke.py rehearsed on the CPU backend, and the two rules it
leans on: ``dev = tpu`` never trains on a CPU it was not pinned to, and
the compile cache lives where the environment (or nothing) puts it.

The rehearsal walks the script's real control flow — LearnTask ->
Trainer -> save_model -> task = serve's ServeServer, and the dp=4 path
on four of the suite's virtual devices — at a toy size with interpreted
kernels. It proves the script, never the chip: its last line says
``cpu``.
"""

import json
import os
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402
from cxxnet_tpu import compile_cache  # noqa: E402
from cxxnet_tpu.parallel import mesh as mesh_mod  # noqa: E402


def _run(capsys, args):
    rc = chip_smoke.main(args)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    phases = {}
    for l in lines:
        if l.startswith('{"phase"'):
            doc = json.loads(l)
            phases[doc["phase"]] = doc
    return rc, lines, phases


def _check_last_line(line, ok):
    doc = json.loads(line)
    assert doc["ok"] is ok
    dev = doc["device"]
    # a rehearsal names the platform it really ran on
    assert dev["platform"] == "cpu" == jax.devices()[0].platform
    assert dev["kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices())
    return doc


def test_rehearsal_trains_saves_and_serves(capsys, tmp_path):
    rc, lines, ph = _run(capsys, ["--rehearse-cpu", "--out", str(tmp_path)])
    assert rc == 0, lines[-5:]
    assert set(_check_last_line(lines[-1], True)) == {"ok", "device"}
    assert ph["device"]["rehearsal"] is True and ph["device"]["jax"]
    tr = ph["train"]
    assert tr["ok"] and tr["compute_dtype"] == "bfloat16"
    assert tr["losses"][-1] < tr["losses"][0]
    # ONE compile of the step (the second call must not retrace), and
    # a convnet's ops choose nothing: no kernel, an empty selection log
    assert tr["compiles_per_step"][0] >= 1
    assert sum(tr["compiles_per_step"][1:]) == 0
    assert tr["selection"] == {} and tr["pallas_kernels_in_step"] == 0
    assert os.path.exists(os.path.join(_REPO, tr["checkpoint"]))
    sv = ph["serve"]
    assert sv["ok"] and len(sv["buckets"]) == 2
    assert sv["executables"] == 4          # 2 buckets x (raw, predict)
    assert sv["max_abs_diff_vs_trainer"] < 1e-3
    # with nothing selected the trainer prints no selection line
    assert not any(l.startswith("selection: ") for l in lines)


def test_rehearsal_four_chips_only_runs_the_dp_path(capsys, tmp_path):
    rc, lines, ph = _run(capsys, ["--rehearse-cpu", "--chips", "4",
                                  "--out", str(tmp_path)])
    assert rc == 0, lines[-5:]
    _check_last_line(lines[-1], True)
    assert "train" not in ph and "serve" not in ph
    dp = ph["dp4"]
    assert dp["ok"] and dp["batch_shards"] == 4
    assert dp["param_leaves_on_four_devices"] is True
    assert dp["all_reduce_in_compiled_step"] > 0
    assert dp["step1_loss_diff"] < dp["bound"]
    assert dp["selection"] == {}


def test_failed_phase_exits_nonzero(capsys, tmp_path, monkeypatch):
    """A check that does not hold fails the run: here the loss rises."""
    monkeypatch.setattr(
        chip_smoke, "take_steps",
        lambda phase, tr, staged, n: ([1.0, 2.0], [0.1, 0.1], [1, 0]))
    rc, lines, ph = _run(capsys, ["--rehearse-cpu", "--out", str(tmp_path)])
    assert rc == 1
    assert ph["train"]["ok"] is False
    assert "did not decrease" in ph["train"]["error"]
    assert _check_last_line(lines[-1], False)["failed"] == "train"


def test_without_the_rehearsal_flag_a_cpu_is_a_failure(capsys, tmp_path):
    """No accelerator: non-zero exit, and NO result on stdout."""
    rc = chip_smoke.main(["--out", str(tmp_path)])
    cap = capsys.readouterr()
    assert rc not in (0, 1)
    assert cap.out.strip() == ""
    assert "'cpu'" in cap.err and "'tpu'" in cap.err


# -- dev = tpu on a CPU backend ------------------------------------------------

def test_dev_tpu_on_an_unpinned_cpu_backend_raises(monkeypatch):
    """Where JAX found no chip and quietly gave the CPU backend, a
    ``dev = tpu`` run stops, naming both platforms."""
    monkeypatch.setattr(mesh_mod, "_cpu_pinned", lambda: False)
    with pytest.raises(RuntimeError) as e:
        mesh_mod.make_mesh_context("tpu")
    assert "'tpu'" in str(e.value) and "'cpu'" in str(e.value)
    with pytest.raises(RuntimeError):
        mesh_mod.devices_for("tpu:0")
    # dev = cpu is what it says, pinned or not
    assert mesh_mod.devices_for("cpu")[0].platform == "cpu"


def test_dev_tpu_on_the_pinned_cpu_backend_runs_and_says_so(
        monkeypatch, capsys):
    """The suite's own case: pinned on purpose, every ``dev = tpu``
    example config keeps running, and one line says the pin won."""
    assert mesh_mod._cpu_pinned()          # tests/conftest.py's pin
    monkeypatch.setattr(mesh_mod, "_PIN_NOTED", False)
    ctx = mesh_mod.make_mesh_context("tpu")
    assert ctx.num_devices == len(jax.devices())
    assert [d.platform for d in mesh_mod.devices_for("tpu:0-1")] \
        == ["cpu", "cpu"]
    out = capsys.readouterr().out
    assert out.count("overridden by the JAX_PLATFORMS=cpu pin") == 1


# -- where the compile cache lives ----------------------------------------------

def test_cache_dir_rule(monkeypatch, tmp_path):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    # nothing set: the fixed directory in the checkout
    assert compile_cache.resolve_cache_dir() \
        == os.path.join(_REPO, ".jax_cache") == compile_cache.DEFAULT_DIR
    assert compile_cache.resolve_cache_dir(str(tmp_path / "cfg")) \
        == str(tmp_path / "cfg")
    # the environment places the cache: that directory and no other
    monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    assert compile_cache.resolve_cache_dir() == env_dir
    assert compile_cache.resolve_cache_dir(env_dir) == env_dir
    with pytest.raises(ValueError, match="disagrees"):
        compile_cache.resolve_cache_dir(str(tmp_path / "elsewhere"))


def test_enable_sets_the_environments_directory_and_no_other(
        monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    env_dir = str(tmp_path / "from_env")
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        return real_update(name, value)
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(compile_cache, "_ENABLED_DIR", "")
    monkeypatch.setattr(jax.config, "update", spy)
    try:
        # CPU backend, nothing placed: the default stays off
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.enable_compile_cache() == ""
        assert seen == []
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert compile_cache.enable_compile_cache(env_dir) == env_dir
        assert seen == [env_dir]               # set once, to that value
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert os.path.isdir(env_dir)
        with pytest.raises(ValueError, match="disagrees"):
            compile_cache.enable_compile_cache(str(tmp_path / "cfg"))
    finally:
        monkeypatch.undo()
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
