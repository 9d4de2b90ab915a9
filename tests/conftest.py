"""Test config: every test runs on the CPU backend with 8 virtual devices.

This is the TPU analog of the reference's ps-lite local mode / dev=cpu
fallback (SURVEY §4): multi-device semantics (meshes, collectives,
sharded checkpoints) are exercised without hardware. The pin is on
purpose and total — ``dev = tpu`` example configs run here too, on the
CPU, and say so once (parallel/mesh.py:devices_for); Pallas kernels run
under the interpreter. What the chip's compiler accepts is checked by
tests/test_chip_compile.py against a described chip, and what runs on a
chip by chip_smoke.py — never by this suite.
"""

import os

# Held to the CPU whatever hardware is attached: the env var for the child
# processes tests start, the config for this one (before its backend
# initializes, which the first device query does).
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

# Build a native library from source when missing or stale — binaries are
# not checked in (they are platform-specific and would silently go stale
# when the .cc sources change). test_capi.py builds the C ABI with this;
# the data-plane decoder builds itself on first use (io/native.py).
NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "cxxnet_tpu", "native")


def build_native(lib_name: str, src_name: str):
    """Run build.sh if ``lib_name`` is missing or older than ``src_name``.
    Returns (lib_exists, build_stderr)."""
    import subprocess
    lib = os.path.join(NATIVE_DIR, lib_name)
    src = os.path.join(NATIVE_DIR, src_name)
    if os.path.exists(lib) and os.path.getmtime(lib) >= os.path.getmtime(src):
        return True, ""
    r = subprocess.run(["sh", os.path.join(NATIVE_DIR, "build.sh")],
                       capture_output=True, text=True)
    return os.path.exists(lib), r.stderr


# -- quick tier (ROADMAP 5c) --------------------------------------------------
# `pytest -m quick` must stay under ~5 minutes so the inner loop has a
# tier that cannot cliff the way the bench did. Modules are opted in
# wholesale from measured per-module wall times (see doc/tasks.md
# "Quick test tier" for the measurement recipe); anything slow or
# compile-heavy stays full-suite-only. A module that grows past ~60 s
# should be evicted here rather than letting the tier rot.
QUICK_MODULES = {
    # measured (one process, CPU mesh) ~80-110 s total here, which is
    # comfortably <5 min on the ~3x-slower driver tier. Excluded on
    # measured cost: attention (17 s), examples (27 s), flagship_e2e
    # (74 s), convnet_ops (107 s), seq_parallel (32 s), layer_sweep,
    # trainer, parallel_ext, seq_layers/ext, kaggle_workflow,
    # bench_helpers (builds+traces a scaled flagship).
    "test_accuracy.py",
    "test_binpage.py",
    "test_capi.py",
    "test_config.py",
    # test_elastic.py, test_shard_ckpt.py and test_dataservice.py are
    # NOT module-listed: their fast protocol/format tests carry
    # explicit @pytest.mark.quick marks, while the multi-run LearnTask
    # / subprocess (compile-cache warm restart, steptime-verdict
    # train) tests stay out of the tier
    "test_input_fold.py",
    "test_graph.py",
    "test_import_cxxnet.py",
    "test_io_pipeline.py",
    "test_layers.py",
    "test_lint.py",
    "test_matlab_wrapper.py",
    "test_mixed_precision.py",
    "test_modelhealth.py",
    "test_optim.py",
    "test_resilience.py",
    "test_serve.py",
    "test_serve_fleet.py",
    "test_stream.py",
    "test_telemetry.py",
    "test_tools.py",
    "test_traceparse.py",
    "test_wrapper.py",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "quick: fast tier (pytest -m quick, target <5 min total)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = os.path.basename(str(item.fspath))
        if mod in QUICK_MODULES and "slow" not in item.keywords:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(scope="session")
def mesh8():
    from cxxnet_tpu.parallel import make_mesh_context
    assert len(jax.devices()) == 8
    return make_mesh_context(devices=jax.devices())


@pytest.fixture(scope="session")
def mesh1():
    from cxxnet_tpu.parallel import make_mesh_context
    return make_mesh_context(devices=jax.devices()[:1])
