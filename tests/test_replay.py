"""Deterministic incident replay (doc/tasks.md "Incident replay").

Unit tier (@quick): failpoint @-offset parsing and compensation math,
config-snapshot chunking + hash check, torn-ledger-tail tolerance
(regression: a SIGKILLed writer tears the final line mid-UTF-8),
reconstruction error taxonomy, config-drift loudness, report hints.

E2E tier (tier-1, not quick): one in-process chaos run per compute dtype
— injected ``device.step`` NaN in a NAMED layer, sentinel trip,
rollback two rounds back (save_period=2 leaves the previous round
unsaved, so the replay window spans a COMPLETE comparable round) —
then time-travel back into the trip:

* failpoints off  -> clean counterfactual, the window's completed
  round re-executes to the bitwise-identical recorded loss;
* failpoints on   -> the compensated schedule re-fires the NaN at the
  recorded absolute step with the IDENTICAL ``layer=/kind=``
  provenance string.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from cxxnet_tpu.config import ConfigError, parse_config_string
from cxxnet_tpu.replay import (ConfigDriftError, ReconstructError,
                               compensate_failpoints, diff_config,
                               execute, list_incidents,
                               parse_replay_config, reconstruct)
from cxxnet_tpu.resilience import failpoints
from cxxnet_tpu.resilience.failpoints import FailpointSpecError
from cxxnet_tpu.telemetry.ledger import (config_hash,
                                         plan_config_snapshot,
                                         read_ledger)

# -- failpoint @-offset modes -------------------------------------------------


@pytest.mark.quick
def test_every_phase_parse_and_fire():
    failpoints.clear()
    try:
        failpoints.configure("device.step=every:5@3")
        assert failpoints.active() == {"device.step": "every:5@3"}
        fired = [c for c in range(1, 16)
                 if failpoints.fire("device.step")]
        # (checks + 3) % 5 == 0 -> checks 2, 7, 12
        assert fired == [2, 7, 12]
    finally:
        failpoints.clear()


@pytest.mark.quick
def test_every_phase_zero_equivalent():
    failpoints.clear()
    try:
        failpoints.configure("device.step=every:4@0")
        assert failpoints.active() == {"device.step": "every:4"}
    finally:
        failpoints.clear()


@pytest.mark.quick
def test_prob_skip_replays_rng_stream():
    """prob:p@K must continue the SAME per-site stream p would have
    produced after K draws — and be PYTHONHASHSEED-independent."""
    failpoints.clear()
    try:
        failpoints.configure("io.read=prob:0.5")
        full = [failpoints.fire("io.read")
                for _ in range(40)]
        failpoints.clear()
        failpoints.configure("io.read=prob:0.5@25")
        tail = [failpoints.fire("io.read")
                for _ in range(15)]
        assert tail == full[25:]
        assert failpoints.active() == {"io.read": "prob:0.5@25"}
    finally:
        failpoints.clear()


@pytest.mark.quick
@pytest.mark.parametrize("spec", [
    "device.step=every:0", "device.step=every:3@-1",
    "device.step=every:x", "device.step=every:3@y",
    "io.read=prob:0.1@-2", "io.read=prob:0.1@z",
])
def test_bad_offset_specs_raise(spec):
    failpoints.clear()
    try:
        with pytest.raises(FailpointSpecError):
            failpoints.configure(spec)
    finally:
        failpoints.clear()


@pytest.mark.quick
def test_compensate_failpoints_math():
    spec, notes = compensate_failpoints({"device.step": "every:21"}, 40)
    assert spec == {"device.step": "every:21@19"}
    # original fires at absolute checks 21, 42, 63...; a replay that
    # restarts counting at 40 must fire at its checks 2, 23 (= 42, 63)
    spec, _ = compensate_failpoints({"device.step": "every:43"}, 32)
    assert spec == {"device.step": "every:43@32"}
    spec, _ = compensate_failpoints({"device.step": "prob:0.1"}, 16)
    assert spec == {"device.step": "prob:0.1@16"}
    spec, _ = compensate_failpoints({"device.step": "prob:0.2@5"}, 16)
    assert spec == {"device.step": "prob:0.2@21"}
    spec, notes = compensate_failpoints({"device.step": "once"}, 10)
    assert spec == {} and any("once" in n for n in notes)
    spec, _ = compensate_failpoints({"device.step": "once"}, 0)
    assert spec == {"device.step": "once"}
    # non-step sites pass through unchanged, with a note
    spec, notes = compensate_failpoints({"io.read": "prob:0.01"}, 99)
    assert spec == {"io.read": "prob:0.01"}
    assert any("io.read" in n for n in notes)


# -- config snapshot + namespace ----------------------------------------------


@pytest.mark.quick
def test_snapshot_inline_small():
    pairs = [("a", "1"), ("b", "2")]
    fields, chunks = plan_config_snapshot(pairs)
    assert chunks == [] and fields["config"] == [["a", "1"], ["b", "2"]]


@pytest.mark.quick
def test_snapshot_chunks_large_and_reassembles(tmp_path):
    from cxxnet_tpu.replay.reconstruct import _assemble_config
    pairs = [(f"key_{i:04d}", "v" * 40) for i in range(200)]
    fields, chunks = plan_config_snapshot(pairs)
    assert "config" not in fields
    assert fields["config_chunks"] == len(chunks) and len(chunks) > 1
    # every chunk's pairs line must fit the ledger's line budget
    for ch in chunks:
        assert len(json.dumps(ch["pairs"])) <= 2600
    rs = {"event": "run_start", "run_id": "r", "host": 0,
          "config_hash": config_hash(pairs), **fields}
    evs = [rs] + [{"event": "config_chunk", "run_id": "r", "host": 0,
                   **ch} for ch in chunks]
    out = _assemble_config(evs, rs)
    assert out == [(k, v) for k, v in pairs]
    # a missing chunk (torn tail) and a corrupted one both fail LOUDLY
    with pytest.raises(ReconstructError, match="config-chunks-missing"):
        _assemble_config(evs[:-1], rs)
    evs[1]["pairs"] = [["key_0000", "TAMPERED"]] + evs[1]["pairs"][1:]
    with pytest.raises(ReconstructError,
                       match="config-snapshot-corrupt"):
        _assemble_config(evs, rs)


@pytest.mark.quick
def test_parse_replay_config():
    rc = parse_replay_config(parse_config_string(
        "replay_incident = 2\nreplay_failpoints = 1\n"
        "replay_steps = 9\nreplay_strict = 0\n"))
    assert (rc.incident, rc.failpoints, rc.steps, rc.strict) \
        == (2, 1, 9, 0)
    with pytest.raises(ConfigError, match="replay_incidnet"):
        parse_replay_config([("replay_incidnet", "2")])
    with pytest.raises(ConfigError):
        parse_replay_config([("replay_steps", "-1")])


# -- torn-tail ledger reads (regression) --------------------------------------


@pytest.mark.quick
def test_torn_tail_tolerated(tmp_path, capsys):
    """A writer SIGKILLed mid-line leaves a torn final record — torn
    even mid-multi-byte-UTF-8. read_ledger must keep every complete
    line and count/warn about the garbage instead of crashing."""
    p = tmp_path / "run.jsonl"
    good = [{"schema": 1, "ts": 1.0, "run_id": "r", "host": 0,
             "event": "round_end", "round": i} for i in range(3)]
    blob = b"".join(json.dumps(e).encode() + b"\n" for e in good)
    # tear a 3-byte UTF-8 char in half: text-mode readers explode here
    torn = json.dumps({"event": "sentinel_trip",
                       "reason": "€" * 40}).encode("utf-8")[:60]
    (p).write_bytes(blob + torn)
    evs = read_ledger(str(p))
    assert [e["round"] for e in evs] == [0, 1, 2]
    assert "malformed" in capsys.readouterr().err
    # quiet mode for report tooling
    evs2 = read_ledger(str(p), warn=False)
    assert len(evs2) == 3
    assert capsys.readouterr().err == ""
    from cxxnet_tpu.telemetry.registry import REGISTRY
    assert REGISTRY.get("cxxnet_ledger_read_drops_total") is not None


# -- reconstruction over synthetic ledgers ------------------------------------


def _write_ledger(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def _synth_events(model_dir, run_id="run-a"):
    pairs = [["model_dir", model_dir], ["batch_size", "4"],
             ["seed", "7"]]
    base = {"run_id": run_id, "host": 0}
    return [
        {"event": "run_start", "ts": 1.0, "config": pairs,
         "config_hash": config_hash(pairs),
         "failpoints": {"device.step": "every:43"},
         "failpoint_seed": 0, "nan_layer": "fc2",
         "data_service_seed": 0, "data_service_shards": 0, **base},
        {"event": "round_end", "ts": 2.0, "round": 3, "loss": 0.5,
         "batches": 8, "step_count": 32, **base},
        {"event": "round_end", "ts": 3.0, "round": 4, "loss": 0.25,
         "batches": 8, "step_count": 40, **base},
        {"event": "sentinel_trip", "ts": 4.0, "round": 5,
         "reason": "non-finite loss", "step": 48,
         "losses": [None], "provenance": "layer=fc2 kind=param",
         **base},
        {"event": "rollback", "ts": 4.1, "round": 5, "to_round": 3,
         "path": os.path.join(model_dir, "none.model"), "step": 48,
         "provenance": "layer=fc2 kind=param", **base},
    ]


@pytest.mark.quick
def test_reconstruct_error_taxonomy(tmp_path):
    led = str(tmp_path / "run.jsonl")
    with pytest.raises(ReconstructError, match="no-ledger"):
        reconstruct(led)
    _write_ledger(led, [{"event": "round_end", "round": 0,
                         "run_id": "r", "host": 0}])
    with pytest.raises(ReconstructError, match="no-incidents"):
        reconstruct(led)
    evs = _synth_events(str(tmp_path))
    _write_ledger(led, evs)
    with pytest.raises(ReconstructError, match="bad-incident-index"):
        reconstruct(led, incident=7)
    # no checkpoint on disk at/below the rollback round
    with pytest.raises(ReconstructError, match="no-valid-checkpoint"):
        reconstruct(led)
    # incident with no governing run_start
    _write_ledger(led, evs[1:])
    with pytest.raises(ReconstructError, match="no-run-start"):
        reconstruct(led)
    # run_start predating replay recording (no snapshot at all)
    rs = dict(evs[0])
    del rs["config"], rs["config_hash"]
    _write_ledger(led, [rs] + evs[1:])
    with pytest.raises(ReconstructError, match="no-config-snapshot"):
        reconstruct(led)


@pytest.mark.quick
def test_config_drift_is_loud(tmp_path):
    led = str(tmp_path / "run.jsonl")
    _write_ledger(led, _synth_events(str(tmp_path)))
    recorded = [("model_dir", str(tmp_path)), ("batch_size", "4"),
                ("seed", "7")]
    live = [("model_dir", str(tmp_path)), ("batch_size", "8"),
            ("seed", "7")]
    diffs = diff_config(recorded, live)
    assert len(diffs) == 1 and "batch_size" in diffs[0][0]
    with pytest.raises(ConfigDriftError, match="batch_size"):
        reconstruct(led, live_config=live)
    # reordering IS drift in this order-sensitive dialect
    assert diff_config(recorded, [recorded[1], recorded[0],
                                  recorded[2]])
    # non-strict downgrades drift to a warning and proceeds past it
    # (then fails later on the missing checkpoint, proving it got
    # through the drift gate)
    with pytest.raises(ReconstructError, match="no-valid-checkpoint"):
        reconstruct(led, live_config=live, strict=False)


@pytest.mark.quick
def test_report_replay_hints(tmp_path):
    import report as report_mod
    led = str(tmp_path / "run.jsonl")
    _write_ledger(led, _synth_events(str(tmp_path)))
    md = report_mod.generate(led, None, [])
    assert "replay with: `python tools/replay.py" in md
    # trip and rollback are incidents 0 and 1 in file order
    assert f"tools/replay.py {led} --incident 0" in md
    assert f"tools/replay.py {led} --incident 1" in md


# -- rotation pinning is covered in tests/test_shard_ckpt.py ------------------

# -- end-to-end: chaos run -> time-travel back into the trip ------------------

CHAOS_CFG = """
data = train
iter = synthetic
  num_inst = 512
  num_class = 5
  input_shape = 1,1,16
  seed_data = 3
iter = end
netconfig=start
layer[+1:h1] = fullc:fc1
  nhidden = 32
  random_type = xavier
layer[+1:a1] = relu
layer[a1->out] = fullc:fc2
  nhidden = 5
  random_type = xavier
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 64
eta = 0.3
dev = cpu
eval_train = 0
print_step = 0
silent = 1
metric = error
health = 1
num_round = 6
save_period = 2
failpoints = "device.step=every:43"
"""


def _chaos_run(tmpdir, extra=""):
    """6 rounds x 8 steps; NaN injected into fc2 at step 43 (round 5);
    save_period=2 leaves round 4 unsaved, so the rollback lands on
    round 3 and the replay window [4, 5] contains one COMPLETE
    comparable round."""
    from cxxnet_tpu.main import LearnTask
    ledger = os.path.join(tmpdir, "run.jsonl")
    os.environ["CXXNET_NAN_LAYER"] = "fc2"
    try:
        task = LearnTask(parse_config_string(
            CHAOS_CFG + f"model_dir = {tmpdir}\n"
            f"telemetry_ledger = {ledger}\n" + extra))
        task.run()
    finally:
        failpoints.clear()
        os.environ.pop("CXXNET_NAN_LAYER", None)
    evs = read_ledger(ledger)
    trips = [e for e in evs if e["event"] == "sentinel_trip"]
    rolls = [e for e in evs if e["event"] == "rollback"]
    assert len(trips) == 1 and len(rolls) == 1, (trips, rolls)
    assert rolls[0]["to_round"] == 3, rolls[0]
    assert trips[0]["provenance"].startswith("layer=fc2 kind=param")
    return ledger, trips[0], rolls[0]


@pytest.fixture(scope="module")
def chaos_std(tmp_path_factory):
    td = str(tmp_path_factory.mktemp("replay_std"))
    return (td,) + _chaos_run(td)


def test_replay_std_clean_counterfactual(chaos_std):
    """Failpoints OFF: the window's completed round (4) re-executes to
    the bitwise-identical recorded round_end loss."""
    td, ledger, trip, roll = chaos_std
    plan = reconstruct(ledger)       # last incident = the rollback
    assert plan.incident["event"] == "rollback"
    assert plan.start_round == 3 and plan.rounds == [4, 5]
    assert plan.start_step == 32
    res = execute(plan, failpoints_on=False,
                  out_ledger=os.path.join(td, "replay_off.jsonl"))
    assert res.verdict == "bit_exact", res.report(plan)
    assert res.compared_rounds[4][2] is True
    rec, rep, _ = res.compared_rounds[4]
    assert rec == rep               # bitwise through the JSON round-trip
    assert res.nan_step is None     # no fault armed -> no NaN
    revs = read_ledger(os.path.join(td, "replay_off.jsonl"))
    assert [e["event"] for e in revs if e["event"].startswith(
        "replay")] == ["replay_start", "replay_verdict"]
    assert revs[-1]["verdict"] == "bit_exact"


def test_replay_std_failpoints_reproduce_nan(chaos_std):
    """Failpoints ON: the compensated schedule (every:43@32) re-fires
    the NaN at the recorded absolute step 43 with the identical
    layer=/kind= provenance string."""
    td, ledger, trip, roll = chaos_std
    plan = reconstruct(ledger, incident=0)    # the sentinel_trip
    assert plan.incident["event"] == "sentinel_trip"
    # detection lags injection by < sentinel_interval: the NaN lands at
    # step 43, the sentinel observes it a few ticks later
    assert plan.target_step == trip["step"]
    assert 43 <= plan.target_step < 43 + 8
    assert plan.replay_failpoints == {"device.step": "every:43@32"}
    res = execute(plan, failpoints_on=True,
                  out_ledger=os.path.join(td, "replay_on.jsonl"))
    assert res.verdict == "bit_exact", res.report(plan)
    assert res.compared_rounds[4][2] is True   # pre-fault round bitwise
    assert res.nan_step == 43                  # the injection step,
    #                                            before the recorded
    #                                            trip's detection at 48
    assert res.provenance_replayed == trip["provenance"]
    assert res.provenance_replayed.startswith("layer=fc2 kind=param")
    revs = read_ledger(os.path.join(td, "replay_on.jsonl"))
    assert revs[-1]["verdict"] == "bit_exact"


def test_replay_verdict_matrix(chaos_std, tmp_path):
    """Tampered records produce the matching non-bit_exact verdicts."""
    import dataclasses
    td, ledger, trip, roll = chaos_std
    plan = reconstruct(ledger, incident=0)
    # a different recorded loss for the completed round -> divergence
    p2 = dataclasses.replace(
        plan, round_losses={4: plan.round_losses[4] + 1e-6})
    res = execute(p2)
    assert res.verdict == "diverged_at_step" and res.step is not None
    # a different recorded batch count -> data addressing changed
    p3 = dataclasses.replace(plan, round_batches={4: 99})
    res = execute(p3)
    assert res.verdict == "unreproducible:batch-count-mismatch"
    # fault armed but recorded provenance names another layer
    p4 = dataclasses.replace(plan, provenance="layer=fc1 kind=param")
    res = execute(p4, failpoints_on=True)
    assert res.verdict == "diverged_at_step"
    assert "provenance" in res.detail
    # checkpoints rotated away entirely -> unreproducible at planning
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    with pytest.raises(ReconstructError, match="no-valid-checkpoint"):
        reconstruct(ledger, incident=0, model_dir=empty)


def test_replay_cli_inprocess(chaos_std, capsys):
    import replay as replay_cli
    td, ledger, trip, roll = chaos_std
    assert replay_cli.main([ledger, "--list"]) == 0
    out = capsys.readouterr().out
    assert "[0] sentinel_trip" in out and "[1] rollback" in out
    rc = replay_cli.main([ledger, "--incident", "0",
                          "--failpoints", "on",
                          "--out-ledger", ""])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "verdict: bit_exact" in out
    assert "layer=fc2 kind=param" in out


def test_replay_bfloat16_path(tmp_path):
    """A bfloat16 run replays bit-exactly too (ISSUE-18 acceptance: the
    std path under both compute dtypes)."""
    td = str(tmp_path)
    ledger, trip, roll = _chaos_run(td, extra="compute_dtype = bfloat16\n")
    plan = reconstruct(ledger, incident=0)
    res = execute(plan, failpoints_on=False)
    assert res.verdict == "bit_exact", res.report(plan)
    assert res.compared_rounds[4][2] is True
    res = execute(plan, failpoints_on=True)
    assert res.verdict == "bit_exact", res.report(plan)
    assert res.nan_step == 43
    assert res.provenance_replayed == trip["provenance"]
