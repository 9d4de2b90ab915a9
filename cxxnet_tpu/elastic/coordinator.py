"""Elastic membership: heartbeats, generations, width agreement.

The cloud-reality analog of the reference's parameter-server tracker
(dmlc_mpi.py kept a static host list for the whole job): here the
worker set CHANGES while the run lives — spot instances get preempted,
replacements join — and the run must agree, without a central service,
on *who is in the fleet right now* and *what dp width the next stretch
of training runs at* (Varuna / Bamboo style checkpoint-reshard
elasticity, PAPERS.md).

Transport is the same one the run ledger already trusts: plain files
in a shared directory (``elastic_dir``), atomic via tmp+rename:

* ``member_<id>.json`` — rewritten every heartbeat tick by its owner:
  ``{"worker", "pid", "capacity", "addr", "ts", "joined_ts"}``. A
  member whose payload ``ts`` is older than ``2 x heartbeat_s`` is
  LOST (SIGKILL, kernel panic, network partition — it cannot tell us).
* ``leave_<id>.json`` — graceful-departure notice (SIGTERM grace path,
  normal completion): peers treat the member as gone IMMEDIATELY
  instead of waiting out the heartbeat timeout.
* ``generation.json`` — the agreed topology: ``{"gen", "members",
  "leader", "width", "complete"}``. The generation counter is
  **monotonically increasing**; every membership change bumps it. The
  bump is performed by the lowest-id LIVE member (one designated
  writer; the write itself is atomic and re-reads the current record,
  so a transient double-bump converges — gen only moves forward).

Width/leader agreement: the **local-mesh mode** (no jax.distributed —
independent processes, the mode the chaos smoke runs) elects the live
member with the largest declared ``capacity`` (ties -> lowest id) as
leader and sets ``width`` to that capacity — exactly one worker trains
at a time on its local dp mesh, the rest are warm standbys that take
over (resharding dp via the rule-driven gather/shard fns) when the
leader is lost. The **jax.distributed mode** (real DCN fleets) keeps
every live member training: ``width = len(members)`` and the
generation bump is followed by a coordinated runtime re-init
(:func:`plan_rendezvous` / :func:`rendezvous_jax_distributed`); this
session's CPU jaxlib cannot run multiprocess computations, so that
path degrades with an explicit SKIP (see doc/elastic_runbook.md).

Observability: ``elastic_join`` / ``elastic_leave`` /
``topology_change`` ledger events, ``cxxnet_elastic_generation``
gauge, ``cxxnet_topology_changes_total`` counter.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ..telemetry.ledger import LEDGER
from ..telemetry.registry import REGISTRY


class TopologyChanged(RuntimeError):
    """Raised out of the round loop when the agreed generation moved
    and this worker's role (leader/width) no longer matches what it is
    running — unwind, re-sync, re-resume."""

    def __init__(self, state: "ElasticState"):
        super().__init__(
            f"elastic topology changed: gen {state.gen}, "
            f"leader {state.leader}, width {state.width}")
        self.state = state


@dataclasses.dataclass(frozen=True)
class ElasticState:
    """One agreed generation, as read back from ``generation.json``."""
    gen: int
    members: tuple            # sorted live worker ids at agreement time
    leader: int
    width: int
    complete: bool = False

    def to_json(self) -> Dict[str, Any]:
        return {"gen": self.gen, "members": list(self.members),
                "leader": self.leader, "width": self.width,
                "complete": self.complete, "ts": round(time.time(), 3)}


def _atomic_write_json(path: str, payload: Dict[str, Any]) -> None:
    # the shared tmp+fsync+rename(+dir-fsync) helper: elastic_dir is
    # documented to live on a shared filesystem, exactly the case the
    # io layer's durability hardening exists for
    from ..io.stream import write_bytes_atomic
    write_bytes_atomic(path, json.dumps(
        payload, sort_keys=True).encode("utf-8"))


def _read_json(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        # mid-rename race or torn write: treat as absent; the next
        # poll re-reads (writers always go through tmp+rename, so this
        # is transient by construction)
        return None


def agree(live: Dict[int, Dict[str, Any]], jaxdist: bool = False
          ) -> Dict[str, Any]:
    """Pure width/leader agreement over the live member records —
    the rule both modes share, separately testable:

    * local-mesh mode: leader = max capacity (tie -> lowest id),
      width = leader's capacity;
    * jax.distributed mode: leader = lowest id (it hosts the new
      coordinator service), width = number of live members.
    """
    if not live:
        return {"leader": -1, "width": 0}
    if jaxdist:
        leader = min(live)
        return {"leader": leader, "width": len(live)}
    leader = min(live, key=lambda w: (-int(live[w].get("capacity", 1)), w))
    return {"leader": leader,
            "width": max(1, int(live[leader].get("capacity", 1)))}


class ElasticCoordinator:
    """One worker's view of the elastic membership protocol.

    Thread-safety: the heartbeat runs on a daemon thread; everything
    else (join/sync/leave) is called from the task driver's thread.
    ``clock`` is injectable for tests (defaults to ``time.time`` —
    wall time, because liveness is judged across PROCESSES from file
    payloads, where a monotonic clock has no shared epoch)."""

    def __init__(self, directory: str, worker: int, capacity: int,
                 heartbeat_s: float = 5.0, grace_s: float = 10.0,
                 min_workers: int = 1, addr: str = "", host: int = -1,
                 jaxdist: bool = False, silent: bool = False,
                 clock=time.time):
        if worker < 0:
            raise ValueError(f"elastic worker id must be >= 0, got {worker}")
        self.dir = directory
        self.worker = int(worker)
        self.capacity = max(1, int(capacity))
        self.heartbeat_s = float(heartbeat_s)
        self.grace_s = float(grace_s)
        self.min_workers = int(min_workers)
        self.addr = addr
        # telemetry/fleet host id this worker reports under — rides the
        # member record so straggler verdicts (keyed by host) map back
        # to worker ids even when the two id spaces differ
        self.host = int(host) if host >= 0 else int(worker)
        self.jaxdist = bool(jaxdist)
        self.silent = silent
        self.clock = clock
        # per-incarnation identity: pids are ambiguous across hosts
        # sharing elastic_dir (per-host pid spaces), so ownership of a
        # member record is judged by this nonce, not by pid
        import secrets
        self._nonce = secrets.token_hex(8)
        self._hb_lock = threading.Lock()
        self.joined_ts: Optional[float] = None
        # the generation this worker last ACTED on (built a trainer
        # for); sync() reports changed=True relative to it
        self.acted_gen = -1
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._left = False
        self._g_gen = REGISTRY.gauge(
            "cxxnet_elastic_generation",
            "Agreed elastic topology generation (monotonic)")
        self._c_changes = REGISTRY.counter(
            "cxxnet_topology_changes_total",
            "Topology generation bumps performed by this worker")
        os.makedirs(self.dir, exist_ok=True)

    # -- paths -----------------------------------------------------------
    def _member_path(self, worker: int) -> str:
        return os.path.join(self.dir, f"member_{worker:04d}.json")

    def _leave_path(self, worker: int) -> str:
        return os.path.join(self.dir, f"leave_{worker:04d}.json")

    @property
    def _gen_path(self) -> str:
        return os.path.join(self.dir, "generation.json")

    # -- membership ------------------------------------------------------
    def join(self) -> None:
        """Register + start heartbeating. A rejoin after a previous
        graceful leave clears this worker's stale leave notice.
        Fails fast when ANOTHER live process already owns this worker
        id (copy-pasted launch line): two same-id members would both
        pass the leadership check and train/write concurrently for
        the whole run — the one failure mode the generation protocol
        cannot see. A STALE record (dead previous incarnation) is
        taken over normally."""
        cur = _read_json(self._member_path(self.worker))
        if cur and cur.get("nonce") != self._nonce \
                and self.clock() - float(cur.get("ts", 0)) \
                <= 2.0 * self.heartbeat_s:
            raise RuntimeError(
                f"elastic worker id {self.worker} is already LIVE in "
                f"{self.dir} (pid {cur.get('pid')}, heartbeat "
                f"{self.clock() - float(cur.get('ts', 0)):.1f}s ago); "
                "every worker needs a distinct elastic_worker id")
        self.joined_ts = self.clock()
        try:
            os.remove(self._leave_path(self.worker))
        except OSError:
            pass
        self._write_heartbeat()
        self._hb_stop.clear()
        self._left = False
        self._hb_thread = threading.Thread(
            target=self._hb_loop, daemon=True,
            name=f"elastic-heartbeat-{self.worker}")
        self._hb_thread.start()
        LEDGER.event("elastic_join", worker=self.worker,
                     capacity=self.capacity, pid=os.getpid(),
                     addr=self.addr)
        if not self.silent:
            print(f"elastic: worker {self.worker} joined "
                  f"(capacity {self.capacity}, dir {self.dir})",
                  flush=True)

    def _write_heartbeat(self) -> None:
        # locked: write_bytes_atomic's tmp names are now per-call
        # unique (no tearing), but the daemon tick and the driver
        # thread's ack()/join() still race the RENAME — without the
        # lock a stale tick could land after an ack and re-publish the
        # old acting_gen, stalling the handover barrier
        with self._hb_lock:
            _atomic_write_json(self._member_path(self.worker), {
                "worker": self.worker, "pid": os.getpid(),
                "nonce": self._nonce, "host": self.host,
                "capacity": self.capacity, "addr": self.addr,
                "ts": round(self.clock(), 3),
                # the generation this worker is ACTING on — a demoted
                # leader advertises the new gen only after it stopped
                # training, which is what the handover wait keys on
                "acting_gen": self.acted_gen,
                "joined_ts": round(self.joined_ts or self.clock(), 3)})

    def _hb_loop(self) -> None:
        # tick at half the liveness cadence so one missed write (GC
        # pause, slow fs) never reads as a death
        period = max(0.05, self.heartbeat_s / 2.0)
        while not self._hb_stop.wait(period):
            try:
                self._write_heartbeat()
            except OSError:
                pass               # transient fs error: next tick retries

    def members(self, now: Optional[float] = None
                ) -> Dict[int, Dict[str, Any]]:
        """Live member records: heartbeat fresh (payload ts within
        ``2 x heartbeat_s``) and no departure notice."""
        now = self.clock() if now is None else now
        live: Dict[int, Dict[str, Any]] = {}
        try:
            names = os.listdir(self.dir)
        except OSError:
            return live
        leaves = {n for n in names if n.startswith("leave_")}
        for n in names:
            if not (n.startswith("member_") and n.endswith(".json")):
                continue
            rec = _read_json(os.path.join(self.dir, n))
            if not rec or "worker" not in rec:
                continue
            w = int(rec["worker"])
            if f"leave_{w:04d}.json" in leaves:
                continue
            if now - float(rec.get("ts", 0)) > 2.0 * self.heartbeat_s:
                continue            # lost: heartbeat went stale
            live[w] = rec
        return live

    # -- generation agreement --------------------------------------------
    def read_state(self) -> Optional[ElasticState]:
        rec = _read_json(self._gen_path)
        if not rec:
            return None
        return ElasticState(
            gen=int(rec.get("gen", 0)),
            members=tuple(sorted(int(m) for m in rec.get("members", []))),
            leader=int(rec.get("leader", -1)),
            width=int(rec.get("width", 0)),
            complete=bool(rec.get("complete", False)))

    def sync(self) -> ElasticState:
        """Read the membership, bump the generation if it drifted from
        the recorded one (designated bumper: the lowest live id), and
        return the current agreed state. Never blocks."""
        live = self.members()
        cur = self.read_state()
        if cur is not None and cur.complete:
            self._g_gen.set(cur.gen)
            return cur
        live_ids = tuple(sorted(live))
        # drift = the membership moved OR the agreement over the SAME
        # membership changed (a same-id replacement rejoining with a
        # different capacity must retune width/leader — the id set
        # alone cannot see that)
        plan = agree(live, jaxdist=self.jaxdist) if live else None
        drift = cur is None or cur.members != live_ids or (
            plan is not None and (cur.leader != plan["leader"]
                                  or cur.width != plan["width"]))
        if drift and live and min(live) == self.worker:
            cur = self._bump(cur, live, reason=self._drift_reason(
                cur, live_ids))
        if cur is None:
            # no record yet and this worker is not the designated
            # bumper (or no live members visible): report an empty
            # pre-formation state — callers poll
            cur = ElasticState(gen=0, members=live_ids, leader=-1,
                               width=0)
        self._g_gen.set(cur.gen)
        return cur

    @staticmethod
    def _drift_reason(cur: Optional[ElasticState], live_ids: tuple) -> str:
        if cur is None:
            return "init"
        lost = sorted(set(cur.members) - set(live_ids))
        joined = sorted(set(live_ids) - set(cur.members))
        parts = []
        if lost:
            parts.append("lost:" + ",".join(str(w) for w in lost))
        if joined:
            parts.append("join:" + ",".join(str(w) for w in joined))
        # same ids, different agreement: a member's declared capacity
        # changed (same-id replacement) -> width/leader retune
        return "+".join(parts) or "retune"

    def _bump(self, cur: Optional[ElasticState],
              live: Dict[int, Dict[str, Any]], reason: str,
              override_complete: bool = False) -> ElasticState:
        # re-read under the write so a racing bumper's generation is
        # never reused (atomic rename = last writer wins; monotonic
        # max+1 = the counter only moves forward either way) — and so
        # a completion marker that landed since our last sync is
        # honored rather than overwritten by a stale-membership bump
        # (reopen() is the one caller allowed to clear it)
        latest = self.read_state()
        if latest is not None and latest.complete \
                and not override_complete:
            return latest
        base = max(cur.gen if cur else 0, latest.gen if latest else 0)
        plan = agree(live, jaxdist=self.jaxdist)
        st = ElasticState(gen=base + 1,
                          members=tuple(sorted(live)),
                          leader=plan["leader"], width=plan["width"])
        _atomic_write_json(self._gen_path, st.to_json())
        self._c_changes.inc()
        LEDGER.event("topology_change", gen=st.gen,
                     members=list(st.members), leader=st.leader,
                     width=st.width, reason=reason,
                     min_workers=self.min_workers)
        if not self.silent:
            print(f"elastic: topology gen {st.gen} ({reason}): "
                  f"members {list(st.members)}, leader {st.leader}, "
                  f"dp width {st.width}", flush=True)
        return st

    # -- role helpers ----------------------------------------------------
    def trainable(self, st: ElasticState) -> bool:
        """Whether ``st`` lets THIS worker run the train loop: it is
        the leader, the fleet meets the ``min_workers`` floor, and the
        run is not complete."""
        return (not st.complete and st.leader == self.worker
                and st.width >= 1
                and len(st.members) >= self.min_workers)

    def raise_on_change(self, acting_width: Optional[int] = None
                        ) -> None:
        """Round-boundary check (installed as the train loop's elastic
        callback): unwind the round loop (TopologyChanged) when this
        worker stopped being the leader or the agreed width moved away
        from the one it is training at. A generation bump that does
        NOT change this worker's role — e.g. a standby joining — is
        simply acknowledged: unwinding would re-resume for nothing."""
        st = self.sync()
        if not self.trainable(st) or (acting_width is not None
                                      and st.width != acting_width):
            raise TopologyChanged(st)
        if st.gen != self.acted_gen:
            self.ack(st)

    def wait_handover(self, st: ElasticState,
                      timeout_s: Optional[float] = None) -> bool:
        """New-leader settle barrier: block until every OTHER live
        member's heartbeat advertises ``acting_gen >= st.gen`` (i.e.
        a demoted leader has unwound its round loop and stopped
        writing checkpoints) or it dies, bounded by ``timeout_s``
        (default: ``grace_s``). Closes the two-writers window on a
        join-triggered leadership change; a LOSS-triggered change has
        no old writer left, so this returns immediately. Returns False
        on timeout (proceed anyway — blob writes are atomic and a shard
        set is only published by its manifest-last write, so the worst
        case is one orphaned round file or a quorum-rejected partial
        set, not corruption; the demoted leader drains its async save —
        shard staging included — BEFORE acking, main.py's handover
        path)."""
        deadline = self.clock() + (self.grace_s if timeout_s is None
                                   else timeout_s)
        while True:
            live = self.members()
            behind = [w for w, rec in live.items()
                      if w != self.worker
                      and int(rec.get("acting_gen", -1)) < st.gen]
            if not behind:
                return True
            if self.clock() >= deadline:
                if not self.silent:
                    print(f"elastic: handover wait timed out; workers "
                          f"{behind} still acting on an older "
                          "generation", flush=True)
                return False
            time.sleep(max(0.05, self.heartbeat_s / 4.0))

    def wait(self, poll_s: Optional[float] = None) -> None:
        """Standby sleep between syncs (heartbeats keep flowing on the
        daemon thread)."""
        time.sleep(poll_s if poll_s is not None
                   else max(0.1, self.heartbeat_s / 2.0))

    def ack(self, st: ElasticState) -> None:
        """Record (and immediately advertise) that this worker is now
        acting on generation ``st.gen`` — leaders call it when a stint
        starts, demoted/standby workers when they stop training. The
        eager heartbeat write shortens the peers' handover wait; an
        already-current gen is a no-op (idle standbys poll-ack every
        tick and must not double the shared-fs write traffic)."""
        if self.acted_gen == st.gen:
            return
        self.acted_gen = st.gen
        try:
            self._write_heartbeat()
        except OSError:
            pass

    def reopen(self, reason: str = "reopen") -> ElasticState:
        """Clear a stale completion marker: a run reusing the same
        ``elastic_dir`` after an earlier run finished (e.g. num_round
        raised, continue=1) must not be bricked by the leftover
        ``complete=true`` — bump a fresh, non-complete generation over
        the live membership. The caller decides staleness (main.py
        checks the model_dir's newest round against ITS num_round)."""
        return self._bump(self.read_state(), self.members(),
                          reason=reason, override_complete=True)

    def mark_complete(self) -> None:
        """Leader-only: record that the run produced its final model so
        standbys exit instead of waiting for a leader forever."""
        st = self.read_state()
        if st is None:
            st = ElasticState(gen=1, members=(self.worker,),
                              leader=self.worker, width=self.capacity)
        done = dataclasses.replace(st, gen=st.gen + 1, complete=True)
        _atomic_write_json(self._gen_path, done.to_json())
        LEDGER.event("topology_change", gen=done.gen,
                     members=list(done.members), leader=done.leader,
                     width=done.width, reason="complete",
                     min_workers=self.min_workers)

    def leave(self, reason: str = "shutdown") -> None:
        """Graceful departure: notice file first (peers react
        immediately, no heartbeat timeout), then stop heartbeating and
        drop the member record."""
        if self._left:
            return
        self._left = True
        try:
            _atomic_write_json(self._leave_path(self.worker), {
                "worker": self.worker, "reason": reason,
                "ts": round(self.clock(), 3)})
        except OSError:
            pass
        self.close()
        try:
            os.remove(self._member_path(self.worker))
        except OSError:
            pass
        LEDGER.event("elastic_leave", worker=self.worker, reason=reason)
        if not self.silent:
            print(f"elastic: worker {self.worker} left ({reason})",
                  flush=True)

    def close(self) -> None:
        """Stop the heartbeat thread (leave() calls this; a crash path
        that never gets here is exactly what the staleness timeout is
        for)."""
        self._hb_stop.set()
        if self._hb_thread is not None and self._hb_thread.is_alive():
            self._hb_thread.join(timeout=5)


# -- jax.distributed rendezvous (DCN mode) ------------------------------------

def plan_rendezvous(state: ElasticState,
                    members: Dict[int, Dict[str, Any]],
                    port: int = 47601) -> Dict[str, Any]:
    """Pure rendezvous plan for the jax.distributed mode: after a
    topology change the survivors re-init the JAX runtime as an
    ``len(members)``-process job. Rank order is the sorted worker-id
    order (deterministic on every survivor); the coordinator service
    lands on the leader's address, on a port salted by the generation
    so a lingering old coordinator socket never accepts the new
    fleet's handshake."""
    ranks = {w: i for i, w in enumerate(sorted(state.members))}
    lead = members.get(state.leader, {})
    host = (lead.get("addr") or "127.0.0.1").split(":")[0]
    return {"coordinator": f"{host}:{port + (state.gen % 1024)}",
            "num_processes": len(state.members),
            "ranks": ranks}


def rendezvous_jax_distributed(plan: Dict[str, Any], worker: int,
                               timeout_s: int = 120,
                               silent: bool = False) -> bool:
    """Tear down and re-initialize jax.distributed per ``plan`` — the
    DCN-mode rendezvous after a generation bump. Returns True when the
    runtime came back up at the new process count.

    Degrades honestly: a rendezvous that cannot complete (coordinator
    unreachable, peers missing) gets an explicit SKIP print and False —
    the worker continues on its local mesh (degrade, don't die)."""
    import jax
    try:
        if jax.process_count() > 1 or getattr(
                jax.distributed.global_state, "client", None) is not None:
            jax.distributed.shutdown()
        jax.distributed.initialize(
            coordinator_address=plan["coordinator"],
            num_processes=plan["num_processes"],
            process_id=plan["ranks"][worker],
            initialization_timeout=timeout_s)
        return True
    except Exception as e:
        if not silent:
            print(f"elastic: SKIP jax.distributed rendezvous "
                  f"({type(e).__name__}: {e}) — continuing on the "
                  "local mesh", flush=True)
        return False
