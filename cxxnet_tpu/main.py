"""Task driver CLI: train / finetune / pred / extract_feature / get_weight.

Reference: CXXNetLearnTask (/root/reference/src/cxxnet_main.cpp:26-575) —
config file + ``key=value`` CLI overrides, order-sensitive iterator sections
(``data = train`` .. ``iter = end``), round loop with periodic ``%04d.model``
checkpoints, ``continue=1`` auto-resume from the newest checkpoint, and task
dispatch (Run, :113-116). Same surface here:

    python -m cxxnet_tpu.main config.conf [key=value ...]
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .config import (ConfigPairs, parse_cli_overrides, parse_ckpt_config,
                     parse_config_file, parse_data_service_config,
                     parse_elastic_config, parse_retry_policy,
                     parse_telemetry_config)
from .graph import global_param
from .io.data import DataBatch, close_chain, create_iterator
from .resilience import SentinelAbort, TrainingSentinel, counters, failpoints
from .telemetry import TelemetrySession
from .telemetry.anomaly import install_compile_counter
from .telemetry.disttrace import DISTTRACE, set_trace_identity
from .telemetry.ledger import LEDGER, config_hash, plan_config_snapshot
from .telemetry.trace import NULL_SPAN, TRACER
from .trainer import Trainer
from . import checkpoint as ckpt

_SECTION_KEYS = ("data", "eval", "pred")


def split_sections(cfg: ConfigPairs):
    """Separate iterator sections from global config
    (reference CreateIterators, cxxnet_main.cpp:266-315)."""
    global_cfg: ConfigPairs = []
    sections: List[Tuple[str, str, ConfigPairs]] = []  # (kind, name, pairs)
    cur: Optional[List] = None
    for name, val in cfg:
        if name in _SECTION_KEYS:
            cur = []
            sections.append((name, val, cur))
            continue
        if name == "iter":
            if cur is None:
                continue
            if val == "end":
                cur = None
            else:
                cur.append((name, val))
            continue
        if cur is not None:
            cur.append((name, val))
        else:
            global_cfg.append((name, val))
    return global_cfg, sections


def _open_out(path: str, mode: str = "w"):
    """Output stream for pred/extract/get_weight results — local or
    remote (gs:// etc) through the io.stream seam. mode 'w' = text,
    'wb' = binary (output_format = bin)."""
    import io as _io
    from .io import stream
    if stream.is_remote(path):
        raw = stream.sopen(path, "wb")
        return raw if mode == "wb" else _io.TextIOWrapper(
            raw, encoding="utf-8")
    return open(path, mode)


def _text_out(path: str):
    return _open_out(path, "w")


def _round_spans(first: int, end: int):
    """``range(first, end)``, recording a ``train.round`` span around
    the loop's body for each round (from the yield to the request for
    the next: a ``continue`` included, a raise left out)."""
    for r in range(first, end):
        t0 = time.perf_counter()
        yield r
        TRACER.add_complete("train.round", t0, time.perf_counter(),
                            cat="setup", args={"round": r})


class LearnTask:
    def __init__(self, cfg: ConfigPairs):
        # set-up's own span (setup.task) and the compile instrument's
        # (compile.*) land once the session below has set what the
        # tracer keeps
        t_setup = time.perf_counter()
        install_compile_counter()
        self.cfg = cfg
        self.global_cfg, self.sections = split_sections(cfg)
        gp = lambda n, d: global_param(self.global_cfg, n, d)
        self.task = gp("task", "train")
        self.net_type = gp("net_type", "")
        self.num_round = int(gp("num_round", "10"))
        # cap on rounds run THIS invocation (reference cxxnet_main.cpp:
        # 458-459: resume at round 30 with max_round=5 runs 5 rounds);
        # 0 = unlimited (the reference default is INT_MAX)
        self.max_round = int(gp("max_round", "0"))
        self.start_counter = int(gp("start_counter", "0"))
        self.print_step = int(gp("print_step", "100"))
        self.save_period = int(gp("save_period", "1"))
        self.save_model = int(gp("save_model", "1"))
        self.model_dir = gp("model_dir", "./models")
        self.model_in = gp("model_in", "NULL")
        self.continue_training = int(gp("continue", "0"))
        self.extract_node_name = gp("extract_node_name", "top")
        # the pred section's value IS the output filename (reference
        # cxxnet_main.cpp:281-282: ``pred = test.txt``); explicit
        # name_pred= still overrides
        pred_name = next((name for kind, name, _ in self.sections
                          if kind == "pred" and name), "")
        self.name_pred = gp("name_pred", pred_name or "pred.txt")
        self.silent = int(gp("silent", "0"))
        # test_io=1: run the full input pipeline but skip Update — isolates
        # input throughput (reference cxxnet_main.cpp:455-469, doc/debug_perf.md)
        self.test_io = int(gp("test_io", "0"))
        # train_chain=k: fuse k DISTINCT batches into one device dispatch
        # (Trainer.update_chain_batches) — amortizes the per-dispatch
        # host cost for small models. Requires eval_train=0 (chains don't
        # capture train metrics), std mode, update_period=1.
        self.train_chain = int(gp("train_chain", "0"))
        # profile_dir=<path>: capture a profiler trace of the train loop
        # (view with xprof/tensorboard); the reference prescribed external
        # tools only (doc/debug_perf.md) — built-in here
        self.profile_dir = gp("profile_dir", "")
        # -- resilience (doc/tasks.md "Fault tolerance") ------------------
        # fault injection: failpoints = "site=mode,..." config key plus
        # the CXXNET_FAILPOINTS env var (env wins on clashes)
        failpoints.install(gp("failpoints", ""), env=True)
        # transient-IO retry knobs for every remote stream op
        from .io import stream
        stream.set_retry_policy(parse_retry_policy(self.global_cfg))
        # checkpoint hygiene: keep only the newest N (0 = keep all);
        # rounds a sentinel rollback restored stay pinned from rotation
        # (newest keep_incident_rounds of them, 0 disables) so ledger
        # incidents remain replayable after retention trims the rest
        self.keep_last_n = int(gp("keep_last_n", "0"))
        self.keep_incident_rounds = int(gp("keep_incident_rounds", "2"))
        self._incident_rounds: List[int] = []
        # sharded checkpointing + persistent compile cache (doc/tasks.md
        # "Sharded checkpointing"): shard_ckpt routes through the
        # Trainer's knob; compile_cache_dir is enabled below once the
        # telemetry session exists (its ledger event must land)
        self.ckpt_cfg = parse_ckpt_config(self.global_cfg)
        # -- input-data service (doc/tasks.md "Input data service") -------
        # data_service = host:port[,host:port] routes the train data
        # section through the reader fleet (decode paid once per
        # fleet); task=data_reader makes THIS process a reader
        self.data_service = parse_data_service_config(self.global_cfg)
        # -- telemetry (doc/tasks.md "Telemetry") -------------------------
        # telemetry_trace / telemetry_port / telemetry_log /
        # telemetry_profile_steps / telemetry_sync_interval — one
        # validated knob set; the SESSION is built after multi-host
        # bring-up below (exporters are root-rank-only)
        self.telemetry_cfg = parse_telemetry_config(self.global_cfg)
        # loss sentinel: NaN/Inf detection is on by default (sentinel=0
        # disables); spikes trip at sentinel_spike_factor x rolling
        # median (0 disables spike detection only). Every anomaly rolls
        # back to the last VALID checkpoint with the LR scaled by
        # lr_backoff; past max_rollbacks the run aborts with a report.
        self.sentinel_on = int(gp("sentinel", "1"))
        self.sentinel_spike_factor = float(gp("sentinel_spike_factor", "10"))
        self.sentinel_window = int(gp("sentinel_window", "50"))
        self.sentinel_min_history = int(gp("sentinel_min_history", "8"))
        self.max_rollbacks = int(gp("max_rollbacks", "3"))
        self.lr_backoff = float(gp("lr_backoff", "0.5"))
        # check cadence: reading the loss syncs the host to the device
        # step, so a per-step check would serialize the dispatch overlap
        # the prefetch pipeline exists for. Default 8 amortizes the sync
        # to 1-in-8 steps; NaN poisons every subsequent loss (the params
        # carry it), so detection lands <8 steps late and the rollback
        # absorbs the difference. Set 1 for per-step fidelity (catches
        # one-step transient spikes too).
        self.sentinel_interval = max(1, int(gp("sentinel_interval", "8")))
        self.sentinel: Optional[TrainingSentinel] = None
        # model-health probe (doc/tasks.md "Model health"): built per
        # _train_rounds when the trainer carries in-step health stats;
        # syncs on its own (or the sentinel's) interval and feeds the
        # sentinel's grad_norm parameter
        self.health_probe = None
        self._health_every = self.sentinel_interval
        # -- elastic training (doc/tasks.md "Elastic training") -----------
        # elastic_dir set = the train task runs as an elastic worker:
        # membership + heartbeats + generation agreement, topology-
        # change resume onto a new dp width, SIGTERM-grace preemption
        self.elastic = parse_elastic_config(self.global_cfg)
        self._preempt = None          # PreemptHandler during elastic runs
        self._elastic_cb = None       # per-round topology check
        self._elastic_step_cb = None  # heartbeat-gated per-step check
        self._cur_round: Optional[int] = None
        # dev = cpu pins the CPU backend, and must do so BEFORE the first
        # device query (jax.process_index below) initializes another one
        # (parallel/mesh.py:devices_for does the same for Trainer-only
        # embedders, and checks every other dev against what JAX found)
        if gp("dev", "").split(":")[0] == "cpu":
            import jax
            jax.config.update("jax_platforms", "cpu")
        # multi-host bring-up before any device queries (rabit::Init analog)
        from .parallel import maybe_distributed_init
        maybe_distributed_init(self.global_cfg)
        # non-zero ranks suppress progress logging (reference TrackerPrint,
        # utils.h:103-113); checkpoint *collectives* still run on every rank
        # (Trainer.save_model gathers everywhere, writes on rank 0 only) so
        # model-sharded params never deadlock on a one-sided gather
        import jax
        self._is_root = jax.process_index() == 0
        if not self._is_root:
            self.silent = 1
            # non-root ranks keep the step-time probe (it is local and
            # silent) but must not bind the scrape port or clobber the
            # root's trace/log files — root-only observability, same
            # policy as progress logging. The FLEET paths (snapshot
            # push, ledger appends) stay on for every rank: they are
            # per-host by design (host field / host_<k>.json).
            import dataclasses as _dc
            self.telemetry_cfg = _dc.replace(
                self.telemetry_cfg, port=0, trace_path="", log_path="")
        # fleet host identity: telemetry_host overrides (independent
        # processes without jax.distributed, e.g. tools/smoke_fleet.py);
        # default is the jax process index
        self._tel_host = (self.telemetry_cfg.host
                          if self.telemetry_cfg.host >= 0
                          else jax.process_index())
        # run identity must AGREE across ranks of one jax.distributed
        # run: auto-generated ids are per-process (time+pid+random), so
        # host 0's aggregator would reject every other rank's snapshots
        # as previous-run leftovers and the shared ledger would carry N
        # disjoint run_ids. With no explicit telemetry_run_id /
        # CXXNET_RUN_ID, rank 0 generates and broadcasts.
        if not (self.telemetry_cfg.run_id
                or os.environ.get("CXXNET_RUN_ID")) \
                and jax.process_count() > 1:
            import dataclasses as _dc
            from .telemetry.ledger import new_run_id
            rid = new_run_id() if jax.process_index() == 0 else ""
            from jax.experimental import multihost_utils
            buf = np.zeros(64, np.uint8)
            b = rid.encode("ascii")[:64]
            buf[:len(b)] = np.frombuffer(b, np.uint8)
            out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
            rid = bytes(out).rstrip(b"\x00").decode("ascii")
            self.telemetry_cfg = _dc.replace(self.telemetry_cfg, run_id=rid)
        # the session enables the tracer and starts the JSONL logger /
        # standalone /metrics endpoint immediately; run() closes it
        # (trace dump + final log flush). Built in __init__, not run(),
        # so tools that drive task_* methods directly still get a live
        # session.
        self.telemetry = TelemetrySession(
            self.telemetry_cfg, silent=bool(self.silent),
            cfg_hash=config_hash(self.cfg), host=self._tel_host)
        if self.telemetry_cfg.trace_path:
            # name this process's track in tools/trace_assemble.py's
            # merged fleet trace (the reader refines this with its
            # service endpoint when it binds)
            set_trace_identity(role=self.task)
        # persistent compile cache BEFORE the first executable builds
        # (train step fns, serve buckets): warm restarts — elastic
        # takeovers, replica cold-starts, continue=1 — deserialize
        # instead of recompiling (cxxnet_compile_cache_hits_total)
        from .compile_cache import enable_compile_cache
        enable_compile_cache(self.ckpt_cfg.compile_cache_dir,
                             silent=bool(self.silent))
        self.trainer = Trainer(self.global_cfg)
        # the hang watchdog's progress source upgrades to the trainer's
        # own step counter — it advances even with the step-time probe
        # disabled (telemetry_steptime=0), so the watchdog stays armed
        if self.telemetry.watchdog is not None:
            tr = self.trainer
            self.telemetry.watchdog.progress_fn = \
                lambda: tr._step_count
        # run_start anchors the ledger: identity + config + the mesh
        # this process actually brought up. The replay fields — the
        # RESOLVED config snapshot (post-parse, post-CLI-override; the
        # env-armed failpoints recorded separately below since they
        # never enter cfg), the armed failpoint spec + its seed/target
        # env, and the data-service addressing seed — are everything
        # replay/reconstruct.py needs to rebuild this run's exact
        # batch-address and fault schedule in one local process.
        from .parallel import mesh as mesh_mod
        from .compile_cache import cache_dir
        m = self.trainer.mesh
        snap_fields, snap_chunks = plan_config_snapshot(self.cfg)
        LEDGER.event(
            "run_start", task=self.task,
            config_hash=self.telemetry.cfg_hash,
            process_count=jax.process_count(),
            process_index=jax.process_index(),
            devices=m.num_devices, platform=jax.devices()[0].platform,
            mesh={"data": m.data_parallel, "seq": m.seq_parallel,
                  "pipe": m.pipeline_parallel, "model": m.model_parallel},
            dist=mesh_mod.LAST_DIST_INIT,
            compile_cache=cache_dir(),
            failpoints=failpoints.active(),
            failpoint_seed=int(os.environ.get(
                failpoints.SEED_ENV_VAR, "0") or "0"),
            nan_layer=os.environ.get("CXXNET_NAN_LAYER", ""),
            data_service_seed=self.data_service.seed,
            data_service_shards=(
                (self.data_service.shards
                 or len(self.data_service.endpoint_list))
                if self.data_service.enabled else 0),
            **snap_fields)
        for ch in snap_chunks:
            LEDGER.event("config_chunk", **ch)
        TRACER.add_complete("setup.task", t_setup, time.perf_counter(),
                            cat="setup")

    # -- iterators ---------------------------------------------------------
    def _make_iter(self, pairs: ConfigPairs):
        # globals (batch_size, input_shape, ...) reach every iterator, then
        # the section-local pairs override
        return create_iterator(self.global_cfg + pairs)

    def train_iter(self):
        for kind, name, pairs in self.sections:
            if kind == "data":
                if self.data_service.enabled \
                        and self.task in ("train", "finetune"):
                    # TRAINING only: eval sections stay local, and the
                    # pred/extract tasks (which fall back to the data
                    # section when no pred section exists) keep the
                    # section's sequential order — output files are a
                    # row-order contract the service's global-shuffle
                    # stream would scramble
                    from .data_service.client import build_service_iterator
                    return build_service_iterator(
                        self.global_cfg + pairs, self.data_service,
                        silent=bool(self.silent))
                return self._make_iter(pairs)
        return None

    def eval_iters(self):
        return [(name, self._make_iter(pairs))
                for kind, name, pairs in self.sections if kind == "eval"]

    def pred_iter(self):
        for kind, name, pairs in self.sections:
            if kind == "pred":
                return self._make_iter(pairs)
        return None

    def _agree_latest(self, want_blob: bool = False):
        """Resolve the continue=1 resume round, and in multi-host runs verify
        every rank resolved the SAME round before anyone loads — ranks that
        scan model_dir independently on non-shared disks would otherwise
        issue mismatched collectives and hang. model_dir must live on a
        filesystem visible to all ranks (doc/multichip.md).

        The scan is find_latest_valid: a checkpoint truncated by a killed
        run is SKIPPED (with its ``.tmp`` orphans swept) and resume falls
        back to the newest round that verifies — crash consistency, not
        just crash detection. ``want_blob`` forwards the verified blob so
        the caller restores without a second archive read."""
        latest = ckpt.find_latest_valid(self.model_dir,
                                        verbose=not self.silent,
                                        want_blob=want_blob)
        import jax
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils
            local = -1 if latest is None else latest[0]
            rounds = np.asarray(multihost_utils.process_allgather(
                np.int32(local))).ravel()
            if len(set(int(x) for x in rounds)) != 1:
                raise RuntimeError(
                    "continue=1: ranks resolved different latest checkpoint "
                    f"rounds {sorted(set(int(x) for x in rounds))}; model_dir "
                    "must be on a shared filesystem visible to every rank "
                    "(see doc/multichip.md)")
        return latest

    # -- model init --------------------------------------------------------
    def _init_model(self) -> None:
        with TRACER.span("setup.weights", cat="setup"):
            self._init_or_restore()

    def _init_or_restore(self) -> None:
        tr = self.trainer
        if self.continue_training:
            latest = self._agree_latest(want_blob=True)
            if latest is not None:
                # restore from the blob the verification scan already
                # read — no second archive read/hash on resume
                r, path, blob = latest
                tr.init_model()
                tr.load_blob(blob)
                self.start_counter = r + 1
                if not self.silent:
                    print(f"continuing from round {r} ({path})")
                return
        if self.model_in != "NULL":
            tr.init_model()
            if self.task == "finetune":
                tr.copy_model_from(self.model_in)
            else:
                tr.load_model(self.model_in)
                self.start_counter = tr.round_counter + 1
            return
        tr.init_model()

    # -- tasks -------------------------------------------------------------
    def run(self) -> None:
        status = "ok"
        try:
            if self.task in ("train", "finetune"):
                self.task_train()
            elif self.task == "pred":
                self.task_predict()
            elif self.task == "pred_raw":
                self.task_predict_raw()
            elif self.task in ("extract", "extract_feature"):
                self.task_extract()
            elif self.task == "get_weight":
                self.task_get_weight()
            elif self.task == "serve":
                self.task_serve()
            elif self.task == "data_reader":
                self.task_data_reader()
            else:
                raise ValueError(f"unknown task {self.task!r}")
        except BaseException as e:
            # the ledger's run_end must name the failure mode — an
            # aborted run with status "ok" would lie to the report tool
            status = f"error:{type(e).__name__}"
            raise
        finally:
            self.telemetry.close(
                ready=self.trainer.last_loss_handle, status=status)

    def task_train(self) -> None:
        if self.elastic.enabled and not self.test_io:
            return self.task_train_elastic()
        tr = self.trainer
        self._init_model()
        itr_train = self.train_iter()
        if itr_train is None:
            raise ValueError("no training data section (data = ...) in config")
        evals = self.eval_iters()
        from .io import stream
        stream.makedirs(self.model_dir)
        if self.profile_dir:
            import jax
            jax.profiler.start_trace(self.profile_dir)
        try:
            self._train_rounds(tr, itr_train, evals)
        finally:
            # a data-service iterator owns sockets + a prefetch
            # thread; any chain can hide a threadbuffer producer —
            # close_chain walks .base so no wrapper has to forward
            close_chain(itr_train)
            # finalize the trace even when the loop dies mid-round — the
            # crashing/interrupted run is the one whose profile matters
            if self.profile_dir:
                import jax
                jax.profiler.stop_trace()
                if not self.silent:
                    print(f"profiler trace written to {self.profile_dir}")
        self._final_save(tr)

    def _final_save(self, tr) -> None:
        """Final-model tail shared by task_train and the elastic
        finish: drain any pending async PERIODIC write tolerantly (its
        failure is covered by the degrade-don't-die contract and must
        not abort before the final model is attempted), write the
        final model if the last round's periodic save didn't, then
        wait STRICTLY — the FINAL write's failure raises, because
        exiting 0 without the artifact the run exists to produce would
        be a lie."""
        if self.save_model and not self.test_io:
            try:
                tr.wait_saves()
            except RuntimeError as e:
                counters.inc("ckpt.write_failures")
                if self._is_root:
                    print(f"WARNING: async checkpoint write failed: {e}; "
                          "attempting the final save anyway", flush=True)
            # the last round actually RUN (max_round may cap below
            # num_round)
            final = tr.checkpoint_path(
                self.model_dir,
                getattr(self, "_end_round", self.num_round) - 1)
            have = ckpt.checkpoint_exists(final)
            import jax
            if jax.process_count() > 1:
                # save_model's gathers are cross-host collectives, so
                # every rank must take the same branch — and the
                # filesystem answer is rank-divergent by construction
                # (rank 0 publishes the blob/manifest while peers are
                # already past their writes). Agree: re-save unless
                # EVERY rank sees the final checkpoint.
                from jax.experimental import multihost_utils
                haves = np.asarray(multihost_utils.process_allgather(
                    np.int32(1 if have else 0))).ravel()
                have = bool(haves.min())
            if not have:
                tr.save_model(final)
        tr.wait_saves()

    # -- elastic training (doc/tasks.md "Elastic training") ----------------
    def task_train_elastic(self) -> None:
        """ROADMAP-4 scenario: the round loop as an elastic worker.
        Membership/heartbeats/generation agreement live in
        ``elastic_dir`` (elastic/coordinator.py); at every leadership
        stint the newest VERIFIED checkpoint is restored onto a mesh
        of the agreed dp width through the rule-driven shard fns
        (elastic/resume.py), so a worker loss mid-run reshards e.g.
        dp 2 -> 1 and resumes at the exact rng/iterator position; a
        SIGTERM preemption notice gets a grace checkpoint and an
        immediate departure notice (elastic/preempt.py). Chaos-proven
        by tools/smoke_elastic.py; runbook: doc/elastic_runbook.md."""
        import jax
        from .elastic import (DemotionAdvisor, ElasticCoordinator,
                              Preempted, PreemptHandler)
        from .elastic import TopologyChanged
        from .elastic import resume as elastic_resume
        from .io import stream
        gp = lambda n, d: global_param(self.global_cfg, n, d)
        if any(int(gp(k, "1")) != 1 for k in
               ("model_parallel", "seq_parallel", "pipeline_parallel")):
            raise ValueError(
                "elastic training composes with data parallelism only "
                "(the dp width IS the elastic degree of freedom); "
                "clear model_parallel/seq_parallel/pipeline_parallel")
        if jax.process_count() > 1:
            raise ValueError(
                "elastic_dir with a jax.distributed multi-rank job is "
                "the DCN mode: drive one single-process worker per "
                "host (examples/multi-machine/elastic_worker.py) and "
                "see doc/elastic_runbook.md for the rendezvous story")
        if not self.save_model or self.save_period < 1:
            # verified checkpoints are the topology-handoff medium AND
            # the completion evidence — without them a takeover
            # restarts from scratch and the completion marker can
            # never be validated (standbys would reopen a finished
            # run forever). save_period=0 ("never save periodically")
            # defeats the handoff just as thoroughly as save_model=0.
            raise ValueError(
                "elastic training requires save_model=1 and "
                "save_period >= 1: periodic verified checkpoints are "
                "how survivors take over and how the completion "
                "marker is validated")
        ndev = len(jax.devices())
        worker = self.elastic.worker if self.elastic.worker >= 0 \
            else self._tel_host
        capacity = self.elastic.capacity or ndev
        if capacity > ndev:
            # an over-declared capacity would win leadership at a
            # width this host cannot actually train at — every ledger
            # record and peer decision would misreport dp. Clamp and
            # say so.
            if self._is_root:
                print(f"WARNING: elastic_capacity={capacity} exceeds "
                      f"this worker's {ndev} local device(s); "
                      f"clamping to {ndev}", flush=True)
            capacity = ndev
        coord = ElasticCoordinator(
            self.elastic.dir, worker=worker, capacity=capacity,
            heartbeat_s=self.elastic.heartbeat_s,
            grace_s=self.elastic.grace_s,
            min_workers=self.elastic.min_workers,
            host=self._tel_host,
            silent=bool(self.silent))
        preempt = PreemptHandler(grace_s=self.elastic.grace_s)
        advisor = DemotionAdvisor()
        tr = None
        try:
            # every side effect (global signal handler, membership
            # registration) happens INSIDE the try: a join that fails
            # fast (duplicate live worker id) must not leak the
            # installed SIGTERM handler or a half-registered member
            preempt.install()
            self._preempt = preempt
            stream.makedirs(self.model_dir)
            coord.join()
            while True:
                st = coord.sync()
                if st.complete:
                    # believe the marker only if the final model
                    # actually covers THIS config's rounds — a
                    # leftover complete=true in a reused elastic_dir
                    # (earlier, shorter run) must reopen, not silently
                    # exit 0 with rounds untrained. The VALIDATING
                    # scan, not the cheap one: a shard-set manifest
                    # whose set cannot actually load (a peer died
                    # between its shards and the publish) must not
                    # count as completion evidence.
                    latest = ckpt.find_latest_valid(self.model_dir,
                                                    sweep_tmp=False)
                    if latest is not None \
                            and latest[0] >= self.num_round - 1:
                        coord.leave("complete")
                        return
                    coord.reopen(
                        reason=f"reopen:num_round={self.num_round}")
                    continue
                if preempt.requested:
                    raise Preempted("preemption notice")
                if not coord.trainable(st):
                    # standby: ack the generation (a demoted leader's
                    # ack is what releases the successor's handover
                    # wait), keep heartbeating, poll
                    coord.ack(st)
                    coord.wait()
                    continue
                # -- leadership stint --------------------------------
                coord.ack(st)
                # join-triggered takeover: wait for the old leader to
                # unwind its round loop before writing checkpoints
                coord.wait_handover(st)
                self._cur_round = None
                tr = self._elastic_trainer(min(st.width, ndev))
                r0 = elastic_resume.resume_latest(
                    tr, self.model_dir, silent=bool(self.silent))
                if r0 is not None:
                    self.start_counter = r0 + 1
                else:
                    # fresh start honoring model_in/finetune; the
                    # resume scan above already covered continue=1
                    self.start_counter = 0
                    saved = self.continue_training
                    self.continue_training = 0
                    try:
                        self._init_model()
                    finally:
                        self.continue_training = saved
                if self.start_counter >= self.num_round:
                    # already fully trained: finish against num_round
                    # — a stale _end_round from an earlier max_round-
                    # capped stint would mislabel the final model and
                    # skip the completion marker
                    self._end_round = self.num_round
                    self._elastic_finish(tr, coord)
                    return
                itr_train = self.train_iter()
                if itr_train is None:
                    raise ValueError(
                        "no training data section (data = ...) in config")
                evals = self.eval_iters()
                self._elastic_cb = self._make_elastic_cb(
                    coord, advisor, st.width)
                self._elastic_step_cb = self._make_elastic_step_cb(
                    coord, st.width)
                try:
                    self._train_rounds(tr, itr_train, evals)
                except TopologyChanged:
                    # drain any in-flight ASYNC checkpoint write before
                    # the loop re-syncs and acks the new generation —
                    # the ack is the successor's license to write, and
                    # it must not fire while our save thread still owns
                    # the round file (save_async=1)
                    try:
                        tr.wait_saves()
                    except RuntimeError as e:
                        counters.inc("ckpt.write_failures")
                        if self._is_root:
                            print(f"WARNING: async checkpoint write "
                                  f"failed during handover: {e}",
                                  flush=True)
                    continue       # demoted / width moved: re-sync
                finally:
                    self._elastic_cb = None
                    self._elastic_step_cb = None
                    # every stint builds a fresh train iterator; a
                    # dropped data-service one would keep fetching the
                    # in-flight epoch (sockets + prefetch thread)
                    close_chain(itr_train)
                self._elastic_finish(tr, coord)
                return
        except Preempted:
            self._elastic_preempt_exit(tr, coord, preempt)
        finally:
            self._elastic_cb = None
            self._elastic_step_cb = None
            self._preempt = None
            preempt.uninstall()
            coord.close()

    def _elastic_trainer(self, width: int) -> Trainer:
        """Build (and adopt) a Trainer over the first ``width`` local
        devices — the agreed dp width of this generation."""
        import jax
        from .parallel import make_mesh_context
        ctx = make_mesh_context(devices=jax.devices()[:max(1, width)])
        tr = Trainer(self.global_cfg, mesh_ctx=ctx)
        self.trainer = tr
        if self.telemetry.watchdog is not None:
            self.telemetry.watchdog.progress_fn = \
                lambda: tr._step_count
        return tr

    def _make_elastic_cb(self, coord, advisor, acting_width: int):
        """Round-boundary elastic housekeeping: feed the straggler-
        demotion advisory from the fleet layer's windowed verdicts,
        then raise TopologyChanged if this worker's role (leadership
        or agreed width) moved."""
        def cb(_r: int) -> None:
            # unconditionally: an EMPTY verdict list is the recovery
            # signal that re-arms the advisory dedupe
            advisor.advise(
                getattr(self.telemetry, "last_straggler_verdicts", []),
                coord.members())
            coord.raise_on_change(acting_width)
        return cb

    def _make_elastic_step_cb(self, coord, acting_width: int):
        """Step-granular demotion poll, gated to at most one
        coordinator sync per heartbeat period: cheap enough to sit in
        the batch loop, frequent enough that a leader whose rounds run
        long still yields within ~a step of losing leadership (the
        abandoned partial round has no checkpoint, so the successor's
        resume stays consistent — same semantics as a SIGKILL)."""
        state = {"next": 0.0}

        def cb() -> None:
            now = time.monotonic()
            if now < state["next"]:
                return
            state["next"] = now + coord.heartbeat_s
            coord.raise_on_change(acting_width)
        return cb

    def _elastic_finish(self, tr, coord) -> None:
        """Final-model tail of an elastic run (shared with task_train),
        then mark the run complete so standbys exit instead of
        electing a leader for a finished job. A stint capped by
        ``max_round`` below ``num_round`` is a budgeted exit, NOT
        completion — marking it complete would block every future
        worker from training the remaining rounds."""
        self._final_save(tr)
        if getattr(self, "_end_round", self.num_round) >= self.num_round:
            coord.mark_complete()
            coord.leave("complete")
        else:
            coord.leave("max_round")

    def _elastic_preempt_exit(self, tr, coord, preempt) -> None:
        """SIGTERM grace path: emergency checkpoint inside the notice
        window (best effort, degradation-tolerant — and only while
        still the leader: a demoted standby must not overwrite its
        successor's rounds), immediate departure notice, exit 0 — a
        preemption is a normal lifecycle event, not a crash."""
        st = coord.read_state()
        r = self._cur_round
        if (tr is not None and tr.params is not None and r is not None
                and self.save_model and st is not None
                and st.leader == coord.worker
                and preempt.remaining_s() > 0):
            path = tr.checkpoint_path(self.model_dir, r)
            if not ckpt.checkpoint_exists(path):
                # partial-round params saved AS round r: the successor
                # resumes at r+1 — freshness over strict determinism
                # inside the preempted round (doc/elastic_runbook.md)
                self._save_round(tr, r)
                try:
                    tr.wait_saves()
                except RuntimeError:
                    counters.inc("ckpt.write_failures")
        coord.leave("preempt")
        if not self.silent:
            print(f"elastic: preempted; grace checkpoint round "
                  f"{r if r is not None else '-'}, left gracefully",
                  flush=True)

    # -- resilience hooks --------------------------------------------------
    def _health_sync(self, tr, r: int):
        """Amortized model-health sync (THE one host sync per
        ``health_interval``): fan the in-trace stat tree out through
        the probe (metrics + detectors), and on an fp16 scaler-overflow
        ONSET run the one-shot grad-provenance walk so the advice event
        names the overflowing layer."""
        hp = self.health_probe
        info = hp.ingest(tr.last_health_handle, round_no=r,
                         step=tr._step_count)
        if info is not None and info.get("overflow_onset"):
            from .telemetry.modelhealth import diagnose_nonfinite
            try:
                prov = diagnose_nonfinite(tr)
            except Exception as e:  # diagnosis must never block training
                prov = f"diagnosis-failed:{type(e).__name__}"
            hp.note_overflow_advice(r, tr._step_count, prov)
        return info

    def _sentinel_step(self, tr, r: int, losses=None,
                       force: bool = False) -> None:
        """Feed the sentinel after a dispatched update; on an anomaly,
        roll back to the newest VALID checkpoint, back off the LR, and
        relabel the trainer to the current round so checkpoint naming
        stays monotonic. Raises :class:`SentinelAbort` when there is
        nothing valid to roll back to or the rollback budget is spent.
        The ``sentinel_interval`` gate amortizes the host-device sync
        for plain AND chain dispatches; ``force=True`` (end of round,
        just before the checkpoint write) bypasses it so a NaN that
        landed between ticks can never be checkpointed. The
        model-health probe syncs here too (its own ``health_interval``
        modulus on the same tick counter) and its in-trace global grad
        norm finally feeds the sentinel's ``grad_norm`` parameter —
        except on fp16 overflow steps, which the loss scaler already
        handled and must not read as hard anomalies."""
        sentinel = self.sentinel
        hp = self.health_probe
        if sentinel is None and hp is None:
            return
        self._sentinel_tick += 1
        if hp is not None \
                and self._sentinel_tick % self._health_every == 0:
            self._health_sync(tr, r)
        if sentinel is None:
            return
        if not force and self._sentinel_tick % self.sentinel_interval:
            return
        if losses is None:
            vals = [tr.last_loss]
        else:          # chain dispatch: the per-step loss vector, host-side
            vals = [float(v) for v in np.asarray(losses).ravel()]
        gn = hp.last_grad_norm if hp is not None else None
        reason = None
        for v in vals:
            reason = sentinel.observe(v, grad_norm=gn)
            if reason:
                break
        if reason is None:
            return
        counters.inc("sentinel.anomalies")
        # one-shot NaN provenance: name the first non-finite layer
        # (param -> activation -> grad walk) BEFORE the rollback wipes
        # the poisoned state — the sentinel record, the ledger events,
        # and the round log all carry it
        prov = None
        if tr.health_on:
            from .telemetry.modelhealth import diagnose_nonfinite
            try:
                prov = diagnose_nonfinite(tr)
            except Exception as e:        # diagnosis must never block recovery
                prov = f"diagnosis-failed:{type(e).__name__}"
            if prov:
                sentinel.annotate_last(prov)
                reason = f"{reason} [{prov}]"
        # step + observed losses make the trip REPLAYABLE: replay
        # re-executes the window and compares this exact step's loss
        # vector (NaN sanitizes to null — a null slot means "non-finite
        # here", which replay asserts positionally)
        LEDGER.event("sentinel_trip", round=r, reason=reason,
                     provenance=prov, step=tr._step_count,
                     losses=vals)
        # drain any in-flight async checkpoint write BEFORE scanning —
        # a failed one degrades (counted) exactly like a sync failure,
        # and the scan must not race a live writer. No tmp sweep here:
        # sweeping belongs to the resume path, where no writer can be
        # live; mid-run the orphans are inert and a sweep could eat a
        # concurrent rank's tmp on a shared filesystem.
        try:
            tr.wait_saves()
        except RuntimeError as e:
            counters.inc("ckpt.write_failures")
            if self._is_root:
                print(f"WARNING: async checkpoint write failed: {e}; "
                      "rolling back to an older checkpoint", flush=True)
        latest = ckpt.find_latest_valid(self.model_dir, sweep_tmp=False,
                                        want_blob=True)
        if latest is None:
            raise SentinelAbort(
                f"training anomaly with no valid checkpoint to roll back "
                f"to: {reason}\n{sentinel.report()}")
        sentinel.record_rollback(latest[0], reason)   # aborts past budget
        r0, path, blob = latest
        # the blob was just read+verified by the scan — restore from it
        # directly (no second archive read). load_blob resets lr_scale
        # to the checkpoint's saved value, so back off from the LOWER of
        # (pre-rollback, checkpoint) scale — repeated rollbacks onto the
        # same checkpoint still compound the backoff.
        scale_before = tr.optimizer.lr_scale
        tr.rollback(path, blob=blob)
        tr.start_round(r)      # keep %04d naming monotonic after restore
        tr.optimizer.lr_scale = min(scale_before, tr.optimizer.lr_scale) \
            * self.lr_backoff
        sentinel.reset_window()
        if hp is not None:
            # the probe's last reading describes the poisoned step; a
            # stale NaN grad norm must not re-trip against restored
            # params
            hp.reset_after_rollback()
        counters.inc("sentinel.rollbacks")
        # pin the restored round from rotation: the ledger incident
        # references it and tools/replay.py must still find it on disk
        # (bounded by keep_incident_rounds in _save_round)
        self._incident_rounds.append(r0)
        LEDGER.event("rollback", round=r, to_round=r0, path=path,
                     reason=reason, provenance=prov, step=tr._step_count,
                     lr_scale=float(tr.optimizer.lr_scale))
        if not self.silent:
            print(f"sentinel: {reason}; rolled back to round {r0} "
                  f"checkpoint ({path}), lr_scale="
                  f"{tr.optimizer.lr_scale:g}", flush=True)

    def _save_round(self, tr, r: int) -> None:
        """Periodic checkpoint write, degradation-tolerant: a failed
        write logs and counts but never kills the run (the next period
        retries; resume simply falls back one more round), then rotation
        trims beyond keep_last_n."""
        # never persist poisoned weights: a step whose apply NaN'd the
        # params AFTER its (finite) loss was computed would otherwise
        # produce a digest-valid NaN checkpoint that every subsequent
        # rollback faithfully restores
        if self.sentinel is not None and not tr.params_finite():
            counters.inc("ckpt.skipped_poisoned")
            if self._is_root:
                print(f"WARNING: skipping checkpoint for round {r}: "
                      "params are non-finite (sentinel will roll back)",
                      flush=True)
            return
        try:
            tr.save_model(tr.checkpoint_path(self.model_dir, r))
        except Exception as e:
            counters.inc("ckpt.write_failures")
            if self._is_root:
                print(f"WARNING: checkpoint write failed for round {r}: "
                      f"{type(e).__name__}: {e}; training continues "
                      "(next save period retries)", flush=True)
            return
        if self.keep_last_n:
            ckpt.rotate_checkpoints(
                self.model_dir, self.keep_last_n,
                pin_rounds=self._incident_rounds,
                keep_incident_rounds=self.keep_incident_rounds)

    def _timed_batches(self, it, probe):
        """Wrap a batch source so each fetch's host-blocked time is
        banked into the step-time probe (data-wait) and traced. Also
        the per-step preemption poll: a SIGTERM notice stops the
        dispatch of further steps HERE (one event check per batch) so
        the grace window is spent writing the emergency checkpoint,
        not finishing the round."""
        it = iter(it)
        while True:
            if self._preempt is not None and self._preempt.requested:
                from .elastic import Preempted
                raise Preempted("preemption notice mid-round")
            if self._elastic_step_cb is not None:
                # heartbeat-gated demotion poll: a leader whose ROUNDS
                # outlast the handover wait must still notice a
                # join-triggered demotion within ~a step, or the
                # successor's timeout would open a two-writers window
                self._elastic_step_cb()
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            t1 = time.perf_counter()
            if probe is not None:
                probe.note_data_wait(t1 - t0)
            TRACER.add_complete("train.data_wait", t0, t1, cat="train")
            yield batch

    @staticmethod
    def _record_step(probe, tr, t_call: float, ready, steps: int = 1):
        """Hand the probe one update call that began at ``t_call``,
        split in two: the enqueue, and the train-metric drain the call
        ended in (a wait for the device — ``Trainer.last_drain_s``)."""
        spent = time.perf_counter() - t_call
        drain = min(tr.last_drain_s, spent)
        probe.record_step(spent - drain, ready=ready, steps=steps,
                          drain_s=drain)

    def _train_rounds(self, tr, itr_train, evals) -> None:
        start = time.time()
        end_round = self.num_round
        if self.max_round > 0:
            end_round = min(end_round, self.start_counter + self.max_round)
        if hasattr(itr_train, "set_epoch"):
            # data-service epochs are addressed, not counted: align the
            # iterator with the resume round so continue=1 / elastic
            # takeovers replay exactly the epoch the uninterrupted run
            # would have served (elastic/resume.py carries the round)
            itr_train.set_epoch(self.start_counter)
        self._end_round = end_round
        self._sentinel_tick = 0
        self._profile_summarized = False
        if self.sentinel_on and not self.test_io:
            if not 0.0 < self.lr_backoff <= 1.0:
                raise ValueError(
                    f"lr_backoff must be in (0, 1], got {self.lr_backoff}")
            self.sentinel = TrainingSentinel(
                spike_factor=self.sentinel_spike_factor,
                window=self.sentinel_window,
                min_history=self.sentinel_min_history,
                max_rollbacks=self.max_rollbacks)
        # step-time breakdown probe: data-wait vs dispatch vs device,
        # syncing at most once per telemetry_sync_interval steps (same
        # amortization as sentinel_interval); verdict joins the round log
        probe = (self.telemetry.make_probe()
                 if self.telemetry_cfg.steptime and not self.test_io
                 else None)
        self._steptime_probe = probe
        # model-health probe: consumes the in-trace per-layer stat tree
        # the trainer's step returns when health=1, syncing on its own
        # interval (default: the sentinel's) — metrics + detectors +
        # the sentinel's grad_norm (doc/tasks.md "Model health")
        self.health_probe = None
        if tr.health_on and not self.test_io:
            from .telemetry.modelhealth import HealthProbe
            self.health_probe = HealthProbe(
                tr.health_cfg, fp16=tr.optimizer.fp16,
                silent=bool(self.silent))
            self._health_every = (tr.health_cfg.interval
                                  or self.sentinel_interval)
        profiler = self.telemetry.profiler
        chain = self.train_chain if self.train_chain > 1 else 0
        if chain and (tr.mesh.pipeline_parallel > 1
                      or (tr.update_period > 1
                          and tr.mesh.seq_parallel > 1)):
            raise ValueError(
                "train_chain composes with dp/tp/sp, train metrics, "
                "and (std-mode) update_period accumulation — but not "
                "with pp, nor with accumulation under sp")
        # per-step ROOT span for distributed tracing: h2d/dispatch spans
        # and the probe's device_block sync nest under it, ledger events
        # emitted inside it carry its trace id, and tail-exemplar mode
        # retains only the slowest steps' trees. The disabled path is
        # one attribute check + the shared no-op span — never a fresh
        # context manager per step.
        def step_span(round_no: int, steps: int = 1):
            if not DISTTRACE.enabled:
                return NULL_SPAN
            return DISTTRACE.span("train.step", cat="train",
                                  args={"round": round_no,
                                        "steps": steps})
        for r in _round_spans(self.start_counter, end_round):
            tr.start_round(r)
            self._cur_round = r      # the grace checkpoint's round label
            batch_count = 0
            n_images = 0
            round_start = time.time()
            # prefetch_device stages batch N+1's H2D + normalize while
            # step N computes (device-side double buffering); train_chain
            # instead stacks k host batches and fuses their steps into
            # one dispatch (the H2D overlap comes from the chain itself)
            batches = (itr_train if (self.test_io or chain)
                       else tr.prefetch_device(itr_train))
            if not self.test_io:
                batches = self._timed_batches(batches, probe)
            pending = []
            pending_rows = 0
            for batch in batches:
                if self.test_io:
                    n_images += batch.batch_size - batch.num_batch_padd
                    batch_count += 1
                    continue
                real_rows = batch.batch_size - batch.num_batch_padd
                if chain:
                    # host copies: iterators may hand out views into
                    # buffers they refill on the next next()
                    pending.append(DataBatch(
                        data=np.array(batch.data),
                        label=np.array(batch.label),
                        num_batch_padd=batch.num_batch_padd,
                        extra_data=[np.array(e)
                                    for e in batch.extra_data],
                        norm=batch.norm))
                    pending_rows += real_rows
                    if len(pending) < chain:
                        continue
                    # progress accounting covers DISPATCHED work only —
                    # queued-but-untrained batches must not inflate
                    # images/sec or read a stale/absent loss
                    if profiler is not None:
                        profiler.maybe_start(tr._step_count)
                    t_d = time.perf_counter()
                    with step_span(r, steps=len(pending)):
                        losses = tr.update_chain_batches(pending)
                        if probe is not None:
                            self._record_step(probe, tr, t_d, losses,
                                              steps=len(pending))
                    if profiler is not None:
                        profiler.maybe_stop(tr._step_count, ready=losses)
                    batch_count += len(pending)
                    n_images += pending_rows
                    pending, pending_rows = [], 0
                    self._sentinel_step(tr, r, losses=losses)
                else:
                    if profiler is not None:
                        profiler.maybe_start(tr._step_count)
                    t_d = time.perf_counter()
                    with step_span(r):
                        tr.update(batch)
                        if probe is not None:
                            self._record_step(probe, tr, t_d,
                                              tr.last_loss_handle)
                    if profiler is not None:
                        profiler.maybe_stop(tr._step_count,
                                            ready=tr.last_loss_handle)
                    n_images += real_rows
                    batch_count += 1
                    self._sentinel_step(tr, r)
                if self.print_step \
                        and batch_count // self.print_step \
                        != (batch_count - (chain or 1)) // self.print_step \
                        and not self.silent:
                    elapsed = int(time.time() - start)
                    ips = n_images / max(time.time() - round_start, 1e-9)
                    print(f"round {r:8d}:[{batch_count:8d}] {elapsed} sec "
                          f"elapsed, loss={tr.last_loss:.6f}, "
                          f"{ips:.1f} images/sec", flush=True)
            for b in pending:      # epoch tail shorter than the chain
                t_d = time.perf_counter()
                with step_span(r):
                    tr.update(b)
                    if probe is not None:
                        self._record_step(probe, tr, t_d,
                                          tr.last_loss_handle)
                n_images += b.batch_size - b.num_batch_padd
                batch_count += 1
                self._sentinel_step(tr, r)
            if self.test_io:
                dt = max(time.time() - round_start, 1e-9)
                print(f"round {r:8d}: test_io {n_images} images in "
                      f"{dt:.2f} sec = {n_images / dt:.1f} images/sec",
                      flush=True)
                continue
            if (profiler is not None and profiler.done
                    and not self._profile_summarized):
                # the telemetry_profile_steps bracket closed this round:
                # print the phase x kind attribution of its steps and
                # the longest idle gaps by train.* span (traceparse),
                # instead of leaving the dump for offline xprof. Root
                # only — non-root ranks must not pay the dump parse for
                # a line they never print.
                self._profile_summarized = True
                att = profiler.summarize() if self._is_root else None
                if att is not None:
                    from .telemetry.traceparse import attribution_fragment
                    print(f"round {r:8d}: {attribution_fragment(att)} "
                          f"(dump: {profiler.dump_dir})", flush=True)
            line = f"round {r:8d}:[{int(time.time() - start)} sec]"
            if tr.eval_train:
                line += tr.train_metric_report("train")
            for name, itr in evals:
                line += tr.evaluate(itr, name)
            if probe is not None:
                # step-time breakdown + input-/compute-bound verdict
                line += probe.report_fragment()
            if self.health_probe is not None:
                # grad-norm / dead-ReLU / loss-scale one-liner + the
                # per-round model_health ledger event
                line += self.health_probe.report_fragment()
                self.health_probe.round_event(r)
            # fleet housekeeping (snapshot push, round_end ledger event,
            # recompile-storm feed) + per-host medians / straggler
            # verdicts on the aggregating host
            dt_round = max(time.time() - round_start, 1e-9)
            line += self.telemetry.round_tick(
                r, images=n_images, batches=batch_count,
                seconds=round(dt_round, 3),
                images_per_sec=round(n_images / dt_round, 2),
                loss=tr.last_loss if batch_count else None,
                step_count=tr._step_count)
            # the metric line always prints on the root rank, even under
            # silent=1 (reference emits it via TrackerPrint regardless)
            if self._is_root:
                print(line, flush=True)
            # save_period == 0 means "never save periodically"
            # (reference cxxnet_main.cpp:220)
            if self.save_model and self.save_period \
                    and (r + 1) % self.save_period == 0:
                # forced (interval-independent) sentinel check first: a
                # NaN that landed between ticks must trigger the
                # rollback BEFORE this round is checkpointed
                self._sentinel_step(tr, r, force=True)
                self._save_round(tr, r)
            # elastic topology check AFTER the checkpoint write: a
            # demotion must never unwind past an unsaved round (the
            # successor resumes from what is on disk)
            if self._elastic_cb is not None:
                self._elastic_cb(r)

    def task_serve(self) -> None:
        """Online inference endpoint (serve/): the request-driven analog
        of the offline pred/pred_raw/extract task modes. Blocks until
        SIGINT/SIGTERM, then drains before exiting."""
        srv = self.build_server()
        srv.start()
        srv.serve_until_interrupt()

    def build_server(self):
        """The ``task = serve`` server, built and not yet started (an
        embedder — chip_smoke.py, a test — starts and stops it itself).
        Single engine by default; any fleet knob (serve_replicas > 1,
        serve_reload_s, serve_ab) builds a replica pool with SLO-aware
        routing and the checkpoint hot-reload watcher."""
        from .config import (ConfigError, parse_quant_config,
                             parse_serve_config)
        from .deploy import DeployController, parse_deploy_config
        from .serve import (CascadeRouter, InferenceEngine, ReloadWatcher,
                            ReplicaPool)
        from .serve.engine import negotiate_blob, restore_inference_blob
        from .serve.server import ServeServer
        sc = parse_serve_config(self.global_cfg)
        dc = parse_deploy_config(self.global_cfg)
        qc = parse_quant_config(self.global_cfg)
        if qc.cascade_enable:
            if dc.enable or sc.reload_s > 0:
                raise ConfigError(
                    "cascade_enable = 1 does not compose with "
                    "deploy_enable/serve_reload_s yet: the cascade "
                    "tiers pin their versions, a reload would swap "
                    "them out from under the router")
            if not qc.cascade_model:
                raise ConfigError(
                    "cascade_enable = 1 needs cascade_model = <path to "
                    "a quantized round> (tools/quantize.py derives one)")
        if dc.enable:
            # the controller owns canary reloads end to end: a plain
            # reload watcher racing it would ship ungated rounds
            if sc.replicas < 2:
                raise ConfigError(
                    "deploy_enable = 1 needs a replica fleet "
                    f"(serve_replicas >= 2, got {sc.replicas}): one "
                    "canary plus at least one incumbent")
            if sc.reload_s > 0:
                raise ConfigError(
                    "deploy_enable = 1 replaces serve_reload_s: the "
                    "deployment controller decides what reloads (set "
                    "serve_reload_s = 0 and use deploy_poll_s)")
            if dc.canary_replicas >= sc.replicas:
                raise ConfigError(
                    f"deploy_canary_replicas ({dc.canary_replicas}) "
                    f"must be < serve_replicas ({sc.replicas}): the "
                    "parity gate compares against a live incumbent")
        # inference-only restore: params + layer state WITHOUT optimizer
        # state (momentum buffers ~double device bytes; an engine never
        # steps the optimizer) — NOT the training path's _init_model.
        # The blob is loaded ONCE and placed per replica in fleet mode.
        model_path = None
        blob = None
        if self.continue_training:
            latest = self._agree_latest(want_blob=True)
            if latest is not None:
                _r, model_path, blob = latest
        if blob is None and self.model_in != "NULL":
            model_path = self.model_in
            blob = ckpt.load_for_inference(model_path)
        if blob is not None and not self.silent:
            print(f"serving model {model_path}", flush=True)
        if blob is None and not self.silent:
            print("serve: no model_in/continue given — serving a "
                  "RANDOMLY INITIALIZED model (smoke mode)", flush=True)

        common = dict(
            buckets=sc.buckets or None, max_batch=sc.max_batch,
            cache_size=sc.cache_size,
            # serve_dtype: serving-side compute dtype override (e.g.
            # serve_dtype=bfloat16 to serve an fp32-trained model at
            # the bf16 matmul rate); default = the net's policy
            dtype=sc.dtype or None)
        watcher = None
        if qc.cascade_enable:
            # two-tier confidence cascade (doc/tasks.md "Quantized
            # serving & cascade"): the flagship blob is the model
            # loaded above, the fast tier loads the PTQ-derived round
            # named by cascade_model. The router IS a pool, so the
            # server front-end is unchanged.
            if blob is None:
                raise ConfigError(
                    "cascade_enable = 1 needs a flagship model "
                    "(model_in or continue = 1)")
            fast_blob = ckpt.load_for_inference(qc.cascade_model)
            pool = CascadeRouter.build_two_tier(
                self.global_cfg,
                flagship_blob=blob,
                flagship_digest=ckpt.blob_digest(blob["meta"]),
                fast_blob=fast_blob,
                fast_digest=ckpt.blob_digest(fast_blob["meta"]),
                qc=qc, n_flagship=sc.replicas,
                n_fast=qc.cascade_replicas,
                flagship_dtype=sc.dtype or None,
                admission_control=bool(sc.admission),
                max_latency_ms=sc.max_latency_ms,
                max_queue_rows=sc.queue_rows,
                default_timeout_ms=sc.timeout_ms or None,
                breaker_threshold=sc.breaker_threshold,
                breaker_reset_s=sc.breaker_reset_s,
                degraded_queue_frac=sc.degraded_queue_frac,
                slo_ms=sc.slo_ms, slo_target=sc.slo_target,
                slo_window_s=sc.slo_window_s,
                slo_burn_degraded=sc.slo_burn_degraded,
                silent=bool(self.silent),
                # per-tier dtype is the whole point here: the fast
                # tier is pinned int8, the flagship follows serve_dtype
                **{k: v for k, v in common.items() if k != "dtype"})
            srv = ServeServer(
                pool=pool, port=sc.port, host=sc.host,
                log_interval_s=sc.log_interval_s,
                silent=bool(self.silent))
        elif sc.fleet:
            pool = ReplicaPool.build(
                self.global_cfg, sc.replicas, blob=blob,
                digest=ckpt.blob_digest(blob["meta"]) if blob else "",
                admission_control=bool(sc.admission),
                max_latency_ms=sc.max_latency_ms,
                max_queue_rows=sc.queue_rows,
                default_timeout_ms=sc.timeout_ms or None,
                breaker_threshold=sc.breaker_threshold,
                breaker_reset_s=sc.breaker_reset_s,
                degraded_queue_frac=sc.degraded_queue_frac,
                slo_ms=sc.slo_ms, slo_target=sc.slo_target,
                slo_window_s=sc.slo_window_s,
                slo_burn_degraded=sc.slo_burn_degraded,
                silent=bool(self.silent), **common)
            if dc.enable:
                # closed-loop deployment: the controller polls the
                # checkpoint directory, gates every new round offline,
                # canaries it, and promotes/rolls back on evidence
                # (doc/tasks.md "Continuous deployment"). Duck-types
                # the watcher's server surface, so the server manages
                # its lifecycle identically.
                watcher = DeployController(
                    pool, self.model_dir, dc,
                    drain_timeout_s=sc.drain_timeout_s,
                    verbose=not self.silent)
            elif sc.reload_s > 0:
                # hot reload watches the checkpoint directory a trainer
                # (this process or another) keeps writing into
                watcher = ReloadWatcher(
                    pool, self.model_dir, interval_s=sc.reload_s,
                    ab_replicas=sc.ab_replicas if sc.ab else 0,
                    drain_timeout_s=sc.drain_timeout_s,
                    verbose=not self.silent)
            srv = ServeServer(
                pool=pool, reload_watcher=watcher,
                port=sc.port, host=sc.host,
                log_interval_s=sc.log_interval_s,
                silent=bool(self.silent))
        else:
            if blob is not None:
                # dtype negotiation (serve.engine.negotiate_blob):
                # serve_dtype=int8 demands a PTQ-derived round; an fp
                # engine dequantizes a quantized one on load
                restore_inference_blob(
                    self.trainer, negotiate_blob(blob, sc.dtype or None))
            else:
                self.trainer.init_model()
            engine = InferenceEngine(self.trainer, **common)
            if blob is not None:
                from .serve.engine import version_name
                engine.weights_digest = ckpt.blob_digest(blob["meta"])
                engine.weights_version = version_name(
                    blob["meta"]["round"]) \
                    + ("-int8" if engine.serve_int8 else "")
            srv = ServeServer(
                engine,
                port=sc.port, host=sc.host,
                max_latency_ms=sc.max_latency_ms,
                max_queue_rows=sc.queue_rows,
                default_timeout_ms=sc.timeout_ms or None,
                log_interval_s=sc.log_interval_s,
                # circuit breaker: N consecutive dispatch failures ->
                # fail-fast 503s until a half-open probe succeeds
                breaker_threshold=sc.breaker_threshold,
                breaker_reset_s=sc.breaker_reset_s,
                degraded_queue_frac=sc.degraded_queue_frac,
                # latency SLO: serve_slo_ms=0 disables tracking; burn
                # rate over serve_slo_burn_degraded flips /healthz to
                # degraded — the admission-control signal a balancer
                # keys on
                slo_ms=sc.slo_ms, slo_target=sc.slo_target,
                slo_window_s=sc.slo_window_s,
                slo_burn_degraded=sc.slo_burn_degraded,
                silent=bool(self.silent))
        return srv

    def task_data_reader(self) -> None:
        """Reader process of the disaggregated input-data service
        (doc/tasks.md "Input data service"): own this rank's shard
        subset of the train data section and serve decoded/augmented/
        batched frames to trainer clients until SIGTERM/SIGINT. The
        trainer side is ``data_service = host:port[,...]`` on an
        ordinary ``task = train`` run."""
        from .data_service.reader import DataReaderServer
        pairs = next((p for kind, _name, p in self.sections
                      if kind == "data"), None)
        if pairs is None:
            raise ValueError(
                "task=data_reader needs a data = train section (the "
                "pipeline it serves)")
        if not self.data_service.enabled or self.data_service.local_only:
            raise ValueError(
                "task=data_reader requires data_service = "
                "host:port[,host:port] naming the reader fleet")
        srv = DataReaderServer(self.global_cfg + pairs,
                               self.data_service,
                               silent=bool(self.silent))
        srv.start()
        srv.serve_until_interrupt()

    def task_predict(self) -> None:
        tr = self.trainer
        self._init_model()
        itr = self.pred_iter() or self.train_iter()
        if itr is None:
            raise ValueError("no pred/data section in config")
        with _text_out(self.name_pred) as f:
            for batch in itr:
                for v in tr.predict(batch):
                    f.write(f"{float(v):g}\n")
        if not self.silent:
            print(f"finished prediction, write into {self.name_pred}")

    def task_predict_raw(self) -> None:
        """Raw top-node rows (e.g. softmax probabilities), one instance per
        line, space-separated — the format the kaggle_bowl submission
        workflow consumes (reference example/kaggle_bowl/pred.conf's
        ``task = pred_raw`` + make_submission.py)."""
        tr = self.trainer
        self._init_model()
        itr = self.pred_iter() or self.train_iter()
        if itr is None:
            raise ValueError("no pred/data section in config")
        with _text_out(self.name_pred) as f:
            for batch in itr:
                for row in tr.predict_raw(batch):
                    f.write(" ".join(f"{float(v):g}" for v in row) + "\n")
        if not self.silent:
            print(f"finished raw prediction, write into {self.name_pred}")

    def _output_txt(self) -> bool:
        """output_format = txt (default) | bin — reference
        cxxnet_main.cpp:145-148 (bin = raw little-endian float32).
        Anything else fails fast: a silently-accepted typo ('Bin',
        'binary') would write text where the consumer expects floats."""
        fmt = global_param(self.global_cfg, "output_format", "txt")
        if fmt not in ("txt", "bin"):
            raise ValueError(
                f"output_format must be 'txt' or 'bin', got {fmt!r}")
        return fmt != "bin"

    def task_extract(self) -> None:
        tr = self.trainer
        self._init_model()
        itr = self.pred_iter() or self.train_iter()
        if itr is None:
            raise ValueError("no pred/data section in config")
        txt = self._output_txt()
        nrow = 0
        with (_text_out(self.name_pred) if txt
              else _open_out(self.name_pred, "wb")) as f:
            for batch in itr:
                feats = tr.extract_feature(batch, self.extract_node_name)
                nrow += feats.shape[0]
                if txt:
                    for row in feats:
                        f.write(" ".join(f"{float(v):g}" for v in row)
                                + "\n")
                else:
                    f.write(np.ascontiguousarray(feats,
                                                 "<f4").tobytes())
        # .meta sidecar: "nrow,c,y,x" (reference cxxnet_main.cpp:418)
        c, y, x = tr.node_shape(self.extract_node_name)
        with _text_out(self.name_pred + ".meta") as f:
            f.write(f"{nrow},{c},{y},{x}\n")
        if not self.silent:
            print(f"finished feature extraction, write into {self.name_pred}")

    def task_get_weight(self) -> None:
        tr = self.trainer
        self._init_model()
        gp = lambda n, d: global_param(self.global_cfg, n, d)
        # reference keys (cxxnet_main.cpp:143-147, TaskGetWeight
        # :335-360); weight_layer/weight_tag are kept as aliases from
        # earlier rounds of this framework
        layer = gp("extract_layer_name", "") or gp("weight_layer", "")
        tag = gp("weight_name", "") or gp("weight_tag", "wmat")
        out_path = gp("weight_filename", "") or self.name_pred
        if not layer:
            raise ValueError(
                "get_weight requires extract_layer_name=<layer>")
        w = tr.get_weight(layer, tag)
        w2 = w.reshape(w.shape[0], -1)
        if self._output_txt():
            with _text_out(out_path) as f:
                for row in w2:
                    f.write(" ".join(f"{float(v):g}" for v in row) + "\n")
        else:
            with _open_out(out_path, "wb") as f:
                f.write(np.ascontiguousarray(w2, "<f4").tobytes())
        # .meta sidecar with the weight shape (cxxnet_main.cpp:354-358)
        with _text_out(out_path + ".meta") as f:
            f.write(" ".join(str(d) for d in w.shape) + "\n")
        if not self.silent:
            print(f"finished getting weight, write into {out_path}")


def main(argv: Optional[List[str]] = None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    ap = argparse.ArgumentParser(
        prog="cxxnet_tpu",
        description="TPU-native cxxnet-capability trainer")
    ap.add_argument("config", help="config file (key=value dialect)")
    ap.add_argument("overrides", nargs="*", help="key=value overrides")
    args = ap.parse_args(argv)
    cfg = parse_config_file(args.config) + parse_cli_overrides(args.overrides)
    LearnTask(cfg).run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
