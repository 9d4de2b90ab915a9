"""Config-file parser for the cxxnet key=value dialect.

Implements the same tokenizing grammar as the reference parser
(/root/reference/src/utils/config.h:20-192): whitespace-separated tokens,
``=`` as its own token, ``#`` line comments, ``"..."`` single-line quoted
strings with backslash escapes, and ``'...'`` multi-line quoted strings.
Order of key=value pairs is preserved because the net-config grammar is
order-sensitive (params attach to the preceding ``layer[...]`` line, iterator
sections run ``data = train`` .. ``iter = end``).

Unlike the reference (which silently stops parsing on a malformed token
stream), malformed input raises :class:`ConfigError`.

Validated config namespaces mostly live here (``serve_*``,
``telemetry_*``, ``io_retry_*``, ...); subsystem-owned namespaces
follow the same ``parse_*`` + ``known``-table contract next to the code
they parameterize — ``deploy_*`` in :mod:`cxxnet_tpu.deploy.policy`,
``elastic_*`` in the elastic package. graftlint's config-namespace pass
harvests every such table, wherever it lives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Tuple

ConfigPairs = List[Tuple[str, str]]


class ConfigError(ValueError):
    """Raised on malformed config input."""


# -- mixed-precision compute policy ------------------------------------------

# accepted spellings of the ``compute_dtype`` config value
_DTYPE_NAMES = {
    "float32": "float32", "fp32": "float32", "f32": "float32",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "float16": "float16", "fp16": "float16", "f16": "float16",
}


@dataclasses.dataclass(frozen=True)
class Policy:
    """Mixed-precision compute policy threaded through the whole stack.

    ``param_dtype`` is the master-copy dtype: parameters and optimizer
    state always live in it (fp32), so checkpoints stay dtype-portable.
    ``compute_dtype`` is what activations/gradients flow in — each layer
    casts its fp32 params to it at apply time (one fused cast per step
    inside jit) and runs its matmul/conv in it. ``output_dtype`` is what
    leaves the model toward the outside world (serve responses, loss
    values, metric reductions) — fp32. Numerically sensitive interior
    math stays fp32 regardless of policy: batch/layer-norm statistics,
    softmax/cross-entropy, attention logits accumulation
    (``preferred_element_type``), and MoE router probabilities.

    The dtype fields hold jnp dtypes; use :func:`parse_policy` to build
    one from a config string.
    """
    param_dtype: Any
    compute_dtype: Any
    output_dtype: Any

    @property
    def reduced(self) -> bool:
        """True when compute runs below the fp32 master precision."""
        return self.compute_dtype != self.param_dtype

    @property
    def needs_loss_scale(self) -> bool:
        """fp16's ~6e-5 .. 65504 range underflows small gradients; bf16
        shares fp32's exponent range and needs no scaling."""
        import jax.numpy as jnp
        return self.compute_dtype == jnp.float16

    @property
    def compute_name(self) -> str:
        import jax.numpy as jnp
        return jnp.dtype(self.compute_dtype).name


# -- auto|1|0 options ---------------------------------------------------------

# accepted spellings of an auto|1|0 config value -> canonical mode
_AUTO_ON_OFF = {
    "auto": "auto", "": "auto",
    "1": "on", "on": "on", "true": "on", "yes": "on",
    "0": "off", "off": "off", "false": "off", "no": "off",
}


def parse_auto_on_off(key: str, val: str) -> str:
    """Canonicalize the value of an auto|1|0 option (``input_fold``) to
    auto|on|off."""
    canon = _AUTO_ON_OFF.get(str(val).strip().lower())
    if canon is None:
        raise ConfigError(
            f"{key} must be one of auto|1|0 (got {val!r})")
    return canon


# -- telemetry ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """The ``telemetry_*`` knob set (doc/tasks.md "Telemetry"). Every
    field's zero value means OFF; an unconfigured run pays nothing."""
    trace_path: str = ""          # telemetry_trace: Chrome-trace JSON out
    trace_capacity: int = 65536   # telemetry_trace_capacity: span ring
    # -- distributed tracing (doc/tasks.md "Distributed tracing") ------
    trace_sample: float = 1.0     # telemetry_trace_sample: root fraction
    trace_tail_pct: float = 0.0   # telemetry_trace_tail_pct: exemplars
    trace_tail_window: int = 128  # telemetry_trace_tail_window: history
    trace_anchor_s: float = 30.0  # telemetry_trace_anchor_s: clock pairs
    sync_interval: int = 8        # telemetry_sync_interval: probe cadence
    port: int = 0                 # telemetry_port: standalone /metrics
    log_path: str = ""            # telemetry_log: JSONL snapshots
    log_interval_s: float = 5.0   # telemetry_log_interval (seconds)
    log_max_kb: int = 1024        # telemetry_log_max_kb: rotate beyond
    profile_steps: str = ""       # telemetry_profile_steps: "a-b"
    profile_dir: str = ""         # telemetry_profile_dir: xprof dump dir
    steptime: int = 1             # telemetry_steptime: 0 disables probe
    # -- fleet observability (doc/tasks.md "Fleet observability") -----
    ledger_path: str = ""         # telemetry_ledger: run-ledger JSONL
    run_id: str = ""              # telemetry_run_id: share across procs
    fleet_dir: str = ""           # telemetry_fleet_dir: snapshot push dir
    push_interval_s: float = 10.0  # telemetry_push_interval (seconds)
    host: int = -1                # telemetry_host: -1 = jax process index
    hang_s: float = 0.0           # telemetry_hang_s: 0 = watchdog off
    hang_dryrun: int = 0          # telemetry_hang_dryrun: 1 = one dump
    straggler_factor: float = 2.0  # telemetry_straggler_factor
    straggler_min_steps: int = 8  # telemetry_straggler_min_steps
    storm_window_s: float = 60.0  # telemetry_storm_window (seconds)
    storm_threshold: int = 8      # telemetry_storm_threshold


def parse_telemetry_config(cfg: ConfigPairs) -> TelemetryConfig:
    """Collect/validate the ``telemetry_*`` keys (last occurrence wins;
    unknown keys in the namespace fail fast, same contract as
    ``io_retry_*``)."""
    known = {
        "telemetry_trace": ("trace_path", str),
        "telemetry_trace_capacity": ("trace_capacity", int),
        "telemetry_trace_sample": ("trace_sample", float),
        "telemetry_trace_tail_pct": ("trace_tail_pct", float),
        "telemetry_trace_tail_window": ("trace_tail_window", int),
        "telemetry_trace_anchor_s": ("trace_anchor_s", float),
        "telemetry_sync_interval": ("sync_interval", int),
        "telemetry_port": ("port", int),
        "telemetry_log": ("log_path", str),
        "telemetry_log_interval": ("log_interval_s", float),
        "telemetry_log_max_kb": ("log_max_kb", int),
        "telemetry_profile_steps": ("profile_steps", str),
        "telemetry_profile_dir": ("profile_dir", str),
        "telemetry_steptime": ("steptime", int),
        "telemetry_ledger": ("ledger_path", str),
        "telemetry_run_id": ("run_id", str),
        "telemetry_fleet_dir": ("fleet_dir", str),
        "telemetry_push_interval": ("push_interval_s", float),
        "telemetry_host": ("host", int),
        "telemetry_hang_s": ("hang_s", float),
        "telemetry_hang_dryrun": ("hang_dryrun", int),
        "telemetry_straggler_factor": ("straggler_factor", float),
        "telemetry_straggler_min_steps": ("straggler_min_steps", int),
        "telemetry_storm_window": ("storm_window_s", float),
        "telemetry_storm_threshold": ("storm_threshold", int),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("telemetry_"):
            if name not in known:
                raise ConfigError(
                    f"unknown telemetry setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    tc = TelemetryConfig(**vals)
    if tc.trace_capacity < 1:
        raise ConfigError(
            f"telemetry_trace_capacity must be >= 1, got "
            f"{tc.trace_capacity}")
    if tc.sync_interval < 1:
        raise ConfigError(
            f"telemetry_sync_interval must be >= 1, got "
            f"{tc.sync_interval}")
    if not 0.0 <= tc.trace_sample <= 1.0:
        raise ConfigError(
            f"telemetry_trace_sample must be in [0, 1], got "
            f"{tc.trace_sample}")
    if not 0.0 <= tc.trace_tail_pct < 100.0:
        raise ConfigError(
            f"telemetry_trace_tail_pct must be in [0, 100) "
            f"(0 = keep every sampled trace), got {tc.trace_tail_pct}")
    if tc.trace_tail_window < 2:
        raise ConfigError(
            f"telemetry_trace_tail_window must be >= 2, got "
            f"{tc.trace_tail_window}")
    if tc.trace_anchor_s <= 0:
        raise ConfigError(
            f"telemetry_trace_anchor_s must be > 0, got "
            f"{tc.trace_anchor_s}")
    if tc.log_max_kb < 1:
        raise ConfigError(
            f"telemetry_log_max_kb must be >= 1, got {tc.log_max_kb}")
    if tc.log_interval_s <= 0:
        raise ConfigError(
            f"telemetry_log_interval must be > 0, got "
            f"{tc.log_interval_s}")
    if tc.push_interval_s <= 0:
        raise ConfigError(
            f"telemetry_push_interval must be > 0, got "
            f"{tc.push_interval_s}")
    if tc.hang_s < 0:
        raise ConfigError(
            f"telemetry_hang_s must be >= 0, got {tc.hang_s}")
    if tc.straggler_factor <= 1.0:
        raise ConfigError(
            f"telemetry_straggler_factor must be > 1, got "
            f"{tc.straggler_factor}")
    if tc.straggler_min_steps < 1:
        raise ConfigError(
            f"telemetry_straggler_min_steps must be >= 1, got "
            f"{tc.straggler_min_steps}")
    if tc.storm_window_s <= 0 or tc.storm_threshold < 1:
        raise ConfigError(
            "telemetry_storm_window must be > 0 and "
            "telemetry_storm_threshold >= 1, got "
            f"{tc.storm_window_s}/{tc.storm_threshold}")
    if tc.profile_steps:
        from .telemetry.profiler import parse_step_range
        try:
            parse_step_range(tc.profile_steps)
        except ValueError as e:
            raise ConfigError(str(e))
        if not tc.profile_dir:
            tc = dataclasses.replace(tc, profile_dir="./profile_dump")
    return tc


# -- serving ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The ``serve_*`` knob set (doc/tasks.md "Serving" / "Serving at
    fleet scale"). One validated namespace, same contract as
    ``telemetry_*``: a typo'd key raises instead of silently serving
    with defaults."""
    port: int = 8080              # serve_port
    host: str = "127.0.0.1"       # serve_host
    buckets: str = ""             # serve_buckets: comma ladder ('' = auto)
    max_batch: int = 64           # serve_max_batch
    cache_size: int = 16          # serve_cache_size
    dtype: str = ""               # serve_dtype: compute-dtype override
    max_latency_ms: float = 5.0   # serve_max_latency_ms
    queue_rows: int = 1024        # serve_queue_rows
    timeout_ms: float = 0.0       # serve_timeout_ms (0 = none)
    log_interval_s: float = 30.0  # serve_log_interval
    breaker_threshold: int = 5    # serve_breaker_threshold (0 = off)
    breaker_reset_s: float = 10.0  # serve_breaker_reset_s
    degraded_queue_frac: float = 0.8  # serve_degraded_queue_frac
    slo_ms: float = 0.0           # serve_slo_ms (0 = SLO tracking off)
    slo_target: float = 0.99      # serve_slo_target
    slo_window_s: float = 60.0    # serve_slo_window_s
    slo_burn_degraded: float = 2.0  # serve_slo_burn_degraded
    # -- fleet (doc/tasks.md "Serving at fleet scale") -----------------
    replicas: int = 1             # serve_replicas: engines in the pool
    reload_s: float = 0.0         # serve_reload_s: ckpt poll (0 = off)
    ab: int = 0                   # serve_ab: 1 = reloads hit canaries only
    ab_replicas: int = 1          # serve_ab_replicas: canary subset size
    admission: int = 1            # serve_admission: 0 disables shedding
    drain_timeout_s: float = 30.0  # serve_drain_timeout_s: reload drain

    @property
    def fleet(self) -> bool:
        """Whether task_serve builds a replica pool (any fleet feature
        requested) instead of the plain single-engine path."""
        return self.replicas > 1 or self.reload_s > 0 or self.ab > 0


def parse_serve_config(cfg: ConfigPairs) -> ServeConfig:
    """Collect/validate the ``serve_*`` keys (last occurrence wins;
    unknown keys in the namespace fail fast)."""
    known = {
        "serve_port": ("port", int),
        "serve_host": ("host", str),
        "serve_buckets": ("buckets", str),
        "serve_max_batch": ("max_batch", int),
        "serve_cache_size": ("cache_size", int),
        "serve_dtype": ("dtype", str),
        "serve_max_latency_ms": ("max_latency_ms", float),
        "serve_queue_rows": ("queue_rows", int),
        "serve_timeout_ms": ("timeout_ms", float),
        "serve_log_interval": ("log_interval_s", float),
        "serve_breaker_threshold": ("breaker_threshold", int),
        "serve_breaker_reset_s": ("breaker_reset_s", float),
        "serve_degraded_queue_frac": ("degraded_queue_frac", float),
        "serve_slo_ms": ("slo_ms", float),
        "serve_slo_target": ("slo_target", float),
        "serve_slo_window_s": ("slo_window_s", float),
        "serve_slo_burn_degraded": ("slo_burn_degraded", float),
        "serve_replicas": ("replicas", int),
        "serve_reload_s": ("reload_s", float),
        "serve_ab": ("ab", int),
        "serve_ab_replicas": ("ab_replicas", int),
        "serve_admission": ("admission", int),
        "serve_drain_timeout_s": ("drain_timeout_s", float),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("serve_"):
            if name not in known:
                raise ConfigError(
                    f"unknown serve setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    sc = ServeConfig(**vals)
    if sc.replicas < 1:
        raise ConfigError(
            f"serve_replicas must be >= 1, got {sc.replicas}")
    if sc.max_batch < 1 or sc.queue_rows < 1 or sc.cache_size < 1:
        raise ConfigError(
            "serve_max_batch, serve_queue_rows and serve_cache_size "
            f"must be >= 1, got {sc.max_batch}/{sc.queue_rows}/"
            f"{sc.cache_size}")
    if sc.breaker_threshold < 0:
        raise ConfigError(
            f"serve_breaker_threshold must be >= 0, got "
            f"{sc.breaker_threshold}")
    if sc.reload_s < 0:
        raise ConfigError(
            f"serve_reload_s must be >= 0, got {sc.reload_s}")
    if sc.ab not in (0, 1):
        raise ConfigError(f"serve_ab must be 0 or 1, got {sc.ab}")
    if sc.ab_replicas < 1:
        raise ConfigError(
            f"serve_ab_replicas must be >= 1, got {sc.ab_replicas}")
    if sc.ab and sc.ab_replicas >= sc.replicas:
        raise ConfigError(
            f"serve_ab_replicas ({sc.ab_replicas}) must be < "
            f"serve_replicas ({sc.replicas}): A/B needs at least one "
            "replica left on the old version")
    if sc.slo_ms > 0 and not 0.0 < sc.slo_target < 1.0:
        raise ConfigError(
            f"serve_slo_target must be in (0, 1), got {sc.slo_target}")
    if sc.drain_timeout_s < 0:
        raise ConfigError(
            f"serve_drain_timeout_s must be >= 0, got "
            f"{sc.drain_timeout_s}")
    return sc


@dataclasses.dataclass(frozen=True)
class LMServeConfig:
    """The ``lm_serve_*`` / ``kv_*`` knob set (doc/tasks.md "LM
    serving"): paged KV-cache geometry plus the continuous-batching
    decode scheduler. Same validated-namespace contract as
    ``serve_*`` — a typo'd key raises instead of silently decoding
    with defaults."""
    kv_block_size: int = 16       # kv_block_size: tokens per cache block
    kv_pool_blocks: int = 64      # kv_pool_blocks: blocks in the pool
    kv_dtype: str = ""            # kv_dtype: cache dtype ('' = compute)
    max_seqs: int = 4             # lm_serve_max_seqs: decode batch rows
    max_context: int = 128        # lm_serve_max_context: prompt+gen cap
    max_new_tokens: int = 32      # lm_serve_max_new_tokens: default cap
    prefill_chunk: int = 16       # lm_serve_prefill_chunk: tokens/step
    max_queue: int = 32           # lm_serve_max_queue: waiting requests
    eos: int = -1                 # lm_serve_eos: stop token (-1 = none)
    role: str = "both"            # lm_serve_role: both|prefill|decode
    handoff_port: int = 0         # lm_serve_handoff_port (0 = ephemeral)
    deadline_ms: float = 0.0      # lm_serve_deadline_ms (0 = none)

    @property
    def max_blocks_per_seq(self) -> int:
        """Block-table width: blocks needed to hold ``max_context``
        tokens (every compiled shape uses this fixed T)."""
        return -(-self.max_context // self.kv_block_size)


def parse_lm_serve_config(cfg: ConfigPairs) -> LMServeConfig:
    """Collect/validate the ``lm_serve_*`` / ``kv_*`` keys (last
    occurrence wins; unknown keys in either namespace fail fast)."""
    known = {
        "kv_block_size": ("kv_block_size", int),
        "kv_pool_blocks": ("kv_pool_blocks", int),
        "kv_dtype": ("kv_dtype", str),
        "lm_serve_max_seqs": ("max_seqs", int),
        "lm_serve_max_context": ("max_context", int),
        "lm_serve_max_new_tokens": ("max_new_tokens", int),
        "lm_serve_prefill_chunk": ("prefill_chunk", int),
        "lm_serve_max_queue": ("max_queue", int),
        "lm_serve_eos": ("eos", int),
        "lm_serve_role": ("role", str),
        "lm_serve_handoff_port": ("handoff_port", int),
        "lm_serve_deadline_ms": ("deadline_ms", float),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("lm_serve_") or name.startswith("kv_"):
            if name not in known:
                raise ConfigError(
                    f"unknown lm-serve setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    lc = LMServeConfig(**vals)
    if lc.kv_block_size < 1 or lc.kv_pool_blocks < 2:
        raise ConfigError(
            "kv_block_size must be >= 1 and kv_pool_blocks >= 2 "
            "(block 0 is reserved scratch), got "
            f"{lc.kv_block_size}/{lc.kv_pool_blocks}")
    if lc.max_seqs < 1 or lc.max_queue < 1:
        raise ConfigError(
            "lm_serve_max_seqs and lm_serve_max_queue must be >= 1, "
            f"got {lc.max_seqs}/{lc.max_queue}")
    if lc.max_context < 1 or lc.max_new_tokens < 1:
        raise ConfigError(
            "lm_serve_max_context and lm_serve_max_new_tokens must be "
            f">= 1, got {lc.max_context}/{lc.max_new_tokens}")
    if lc.prefill_chunk < 1 or lc.prefill_chunk % lc.kv_block_size:
        raise ConfigError(
            f"lm_serve_prefill_chunk ({lc.prefill_chunk}) must be a "
            f"positive multiple of kv_block_size ({lc.kv_block_size}) "
            "so chunk boundaries align with cache blocks")
    if lc.role not in ("both", "prefill", "decode"):
        raise ConfigError(
            f"lm_serve_role must be both|prefill|decode, got {lc.role!r}")
    if lc.deadline_ms < 0:
        raise ConfigError(
            f"lm_serve_deadline_ms must be >= 0, got {lc.deadline_ms}")
    return lc


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """The ``quant_*`` / ``cascade_*`` knob set (doc/tasks.md
    "Quantized serving & cascade"): post-training int8 quantization
    calibration, the drift-verdict thresholds deploy gates on, and the
    two-tier confidence-cascade router. Same validated-namespace
    contract as ``serve_*`` — a typo'd key raises instead of silently
    serving with defaults."""
    calib_batches: int = 4        # quant_calib_batches: activation calib
    calib_percentile: float = 100.0  # quant_calib_percentile (100=absmax)
    max_rel_err: float = 0.05     # quant_max_rel_err: drift gate (RMS)
    max_sat_frac: float = 0.05    # quant_max_sat_frac: |q|==127 fraction
    parity_tol: float = 0.02      # quant_parity_tol: int8-vs-fp accuracy
    # -- cascade (two-tier confidence routing) -------------------------
    cascade_enable: int = 0       # cascade_enable: 1 = route via cascade
    cascade_threshold: float = 0.5  # cascade_threshold: escalate below
    cascade_metric: str = "margin"  # cascade_metric: margin|entropy
    cascade_model: str = ""       # cascade_model: fast-tier (quantized)
    #   checkpoint path ('' = derive by quantizing the flagship blob)
    cascade_replicas: int = 1     # cascade_replicas: fast-tier size


def parse_quant_config(cfg: ConfigPairs) -> QuantConfig:
    """Collect/validate the ``quant_*`` / ``cascade_*`` keys (last
    occurrence wins; unknown keys in either namespace fail fast)."""
    known = {
        "quant_calib_batches": ("calib_batches", int),
        "quant_calib_percentile": ("calib_percentile", float),
        "quant_max_rel_err": ("max_rel_err", float),
        "quant_max_sat_frac": ("max_sat_frac", float),
        "quant_parity_tol": ("parity_tol", float),
        "cascade_enable": ("cascade_enable", int),
        "cascade_threshold": ("cascade_threshold", float),
        "cascade_metric": ("cascade_metric", str),
        "cascade_model": ("cascade_model", str),
        "cascade_replicas": ("cascade_replicas", int),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("quant_") or name.startswith("cascade_"):
            if name not in known:
                raise ConfigError(
                    f"unknown quant/cascade setting {name!r}; valid "
                    "keys: " + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    qc = QuantConfig(**vals)
    if qc.calib_batches < 1:
        raise ConfigError(
            f"quant_calib_batches must be >= 1, got {qc.calib_batches}")
    if not 0.0 < qc.calib_percentile <= 100.0:
        raise ConfigError(
            "quant_calib_percentile must be in (0, 100], got "
            f"{qc.calib_percentile}")
    if qc.max_rel_err <= 0 or qc.max_sat_frac < 0:
        raise ConfigError(
            "quant_max_rel_err must be > 0 and quant_max_sat_frac "
            f">= 0, got {qc.max_rel_err}/{qc.max_sat_frac}")
    if qc.parity_tol <= 0:
        raise ConfigError(
            f"quant_parity_tol must be > 0, got {qc.parity_tol}")
    if qc.cascade_enable not in (0, 1):
        raise ConfigError(
            f"cascade_enable must be 0 or 1, got {qc.cascade_enable}")
    if not 0.0 < qc.cascade_threshold < 1.0:
        raise ConfigError(
            "cascade_threshold must be in (0, 1), got "
            f"{qc.cascade_threshold}")
    if qc.cascade_metric not in ("margin", "entropy"):
        raise ConfigError(
            f"cascade_metric must be margin|entropy, got "
            f"{qc.cascade_metric!r}")
    if qc.cascade_replicas < 1:
        raise ConfigError(
            f"cascade_replicas must be >= 1, got {qc.cascade_replicas}")
    return qc


# -- sharding -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """The rule-driven sharding namespace (doc/tasks.md "Sharding
    rules"). One validated knob set, same contract as ``serve_*`` /
    ``telemetry_*``: a typo'd key raises instead of silently training
    with defaults."""
    partition_rules: str = ""   # custom rules PREPENDED to the model table
    fsdp_axis: str = ""         # mesh axis for at-rest param/opt sharding
    fsdp_min_size: int = 1024   # smallest leaf (elements) worth sharding


# mesh axes a config may name for FSDP-style at-rest sharding: the std
# (GSPMD dp/tp) step only — 'seq'/'pipe' are rejected because the sp/pp
# steps keep their own placement (and a size-1 axis would silently
# no-op, violating this namespace's fail-loud contract)
_FSDP_AXES = ("", "data", "model")


def parse_sharding_config(cfg: ConfigPairs) -> ShardingConfig:
    """Collect/validate ``partition_rules`` / ``fsdp_*`` keys (last
    occurrence wins; unknown keys in the namespace fail fast)."""
    known = {
        "partition_rules": ("partition_rules", str),
        "fsdp_axis": ("fsdp_axis", str),
        "fsdp_min_size": ("fsdp_min_size", int),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("fsdp_") or name.startswith("partition_rule"):
            if name not in known:
                raise ConfigError(
                    f"unknown sharding setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    sc = ShardingConfig(**vals)
    if sc.fsdp_axis not in _FSDP_AXES:
        raise ConfigError(
            f"fsdp_axis must be one of {'|'.join(a for a in _FSDP_AXES if a)}"
            f" (or unset), got {sc.fsdp_axis!r}")
    if sc.fsdp_min_size < 0:
        raise ConfigError(
            f"fsdp_min_size must be >= 0, got {sc.fsdp_min_size}")
    if sc.partition_rules:
        from .parallel.rules import parse_rule_string
        try:
            parse_rule_string(sc.partition_rules)
        except ValueError as e:
            raise ConfigError(f"bad partition_rules value: {e}")
    return sc


# -- checkpoint format + compile cache ----------------------------------------

@dataclasses.dataclass(frozen=True)
class CkptConfig:
    """The sharded-checkpoint / persistent-compile-cache knob set
    (doc/tasks.md "Sharded checkpointing"). One validated namespace,
    same contract as ``serve_*`` / ``telemetry_*``: a typo'd key raises
    instead of silently checkpointing in the wrong format."""
    shard_ckpt: int = 0          # shard_ckpt: 1 = rounds are shard SETS
    shard_ckpt_shards: int = 0   # shard_ckpt_shards: files per set
    #                              (0 = auto: one per jax process)
    compile_cache_dir: str = ""  # compile_cache_dir: persistent XLA
    #                              executable cache ('' = off)


def parse_ckpt_config(cfg: ConfigPairs) -> CkptConfig:
    """Collect/validate the ``shard_ckpt*`` / ``compile_cache_dir``
    keys (last occurrence wins; unknown keys in the namespace fail
    fast)."""
    known = {
        "shard_ckpt": ("shard_ckpt", int),
        "shard_ckpt_shards": ("shard_ckpt_shards", int),
        "compile_cache_dir": ("compile_cache_dir", str),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("shard_ckpt") or \
                name.startswith("compile_cache"):
            if name not in known:
                raise ConfigError(
                    f"unknown checkpoint setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    cc = CkptConfig(**vals)
    if cc.shard_ckpt not in (0, 1):
        raise ConfigError(
            f"shard_ckpt must be 0 or 1, got {cc.shard_ckpt}")
    if cc.shard_ckpt_shards < 0:
        raise ConfigError(
            f"shard_ckpt_shards must be >= 0 (0 = one per process), "
            f"got {cc.shard_ckpt_shards}")
    return cc


# -- elastic training ---------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """The ``elastic_*`` knob set (doc/tasks.md "Elastic training").
    One validated namespace, same contract as ``serve_*`` /
    ``telemetry_*``: a typo'd key raises instead of silently running a
    non-elastic (or wrongly-tuned) job. ``elastic_dir`` set = the train
    task runs as an elastic worker (membership + topology-change resume
    + preemption grace); unset = everything below is inert."""
    dir: str = ""                 # elastic_dir: shared membership dir
    heartbeat_s: float = 5.0      # elastic_heartbeat_s: liveness cadence
    grace_s: float = 10.0         # elastic_grace_s: SIGTERM notice window
    min_workers: int = 1          # elastic_min_workers: train floor
    worker: int = -1              # elastic_worker: -1 = telemetry host id
    capacity: int = 0             # elastic_capacity: dp this worker can
    #                               host (0 = its local device count)

    @property
    def enabled(self) -> bool:
        return bool(self.dir)


def parse_elastic_config(cfg: ConfigPairs) -> ElasticConfig:
    """Collect/validate the ``elastic_*`` keys (last occurrence wins;
    unknown keys in the namespace fail fast)."""
    known = {
        "elastic_dir": ("dir", str),
        "elastic_heartbeat_s": ("heartbeat_s", float),
        "elastic_grace_s": ("grace_s", float),
        "elastic_min_workers": ("min_workers", int),
        "elastic_worker": ("worker", int),
        "elastic_capacity": ("capacity", int),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("elastic_"):
            if name not in known:
                raise ConfigError(
                    f"unknown elastic setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    ec = ElasticConfig(**vals)
    if ec.heartbeat_s <= 0:
        raise ConfigError(
            f"elastic_heartbeat_s must be > 0, got {ec.heartbeat_s}")
    if ec.grace_s < 0:
        raise ConfigError(
            f"elastic_grace_s must be >= 0, got {ec.grace_s}")
    if ec.min_workers < 1:
        raise ConfigError(
            f"elastic_min_workers must be >= 1, got {ec.min_workers}")
    if ec.worker < -1:
        raise ConfigError(
            f"elastic_worker must be >= 0 (or -1 = auto), got "
            f"{ec.worker}")
    if ec.capacity < 0:
        raise ConfigError(
            f"elastic_capacity must be >= 0 (0 = local device count), "
            f"got {ec.capacity}")
    return ec


# -- input-data service -------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DataServiceConfig:
    """The ``data_service*`` knob set (doc/tasks.md "Input data
    service"). One validated namespace, same contract as ``serve_*`` /
    ``telemetry_*``: a typo'd key raises instead of silently training
    off the local pipeline. ``data_service`` set = the train data
    section is served by the reader fleet (or, with the special value
    ``local``, by the same global-shuffle orchestration run
    in-process — the deterministic control / degrade stream); unset =
    everything below is inert."""
    endpoints: str = ""           # data_service: host:port[,host:port]|local
    shards: int = 0               # data_service_shards (0 = one/reader)
    seed: int = 0                 # data_service_seed: global shuffle seed
    cache_batches: int = 256      # data_service_cache: reader LRU frames
    readahead: int = 2            # data_service_readahead: decode-ahead
    timeout_ms: float = 5000.0    # data_service_timeout_ms: fetch timeout
    local_fallback: int = 1       # data_service_local_fallback: 0 = hard
    reader: int = -1              # data_service_reader: this reader's idx
    status_dir: str = ""          # data_service_status_dir: atomic status
    prefetch: int = 2             # data_service_prefetch: client batches
    #                               fetched ahead on a thread (0 = off)

    @property
    def enabled(self) -> bool:
        return bool(self.endpoints.strip())

    @property
    def local_only(self) -> bool:
        return self.endpoints.strip().lower() == "local"

    @property
    def endpoint_list(self) -> List[str]:
        if not self.enabled or self.local_only:
            return []
        return [e.strip() for e in self.endpoints.split(",") if e.strip()]

    @property
    def n_shards(self) -> int:
        return self.shards or max(1, len(self.endpoint_list))

    @staticmethod
    def split_endpoint(endpoint: str) -> Tuple[str, int]:
        host, _, port = endpoint.rpartition(":")
        return host, int(port)


def parse_data_service_config(cfg: ConfigPairs) -> DataServiceConfig:
    """Collect/validate the ``data_service*`` keys (last occurrence
    wins; unknown keys in the namespace fail fast)."""
    known = {
        "data_service": ("endpoints", str),
        "data_service_shards": ("shards", int),
        "data_service_seed": ("seed", int),
        "data_service_cache": ("cache_batches", int),
        "data_service_readahead": ("readahead", int),
        "data_service_timeout_ms": ("timeout_ms", float),
        "data_service_local_fallback": ("local_fallback", int),
        "data_service_reader": ("reader", int),
        "data_service_status_dir": ("status_dir", str),
        "data_service_prefetch": ("prefetch", int),
    }
    vals = {}
    for name, val in cfg:
        if name.startswith("data_service"):
            if name not in known:
                raise ConfigError(
                    f"unknown data_service setting {name!r}; valid "
                    "keys: " + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    dc = DataServiceConfig(**vals)
    if dc.enabled and not dc.local_only:
        for ep in dc.endpoint_list:
            host, _, port = ep.rpartition(":")
            if not host or not port.isdigit():
                raise ConfigError(
                    f"data_service endpoint {ep!r} is not host:port "
                    "(or the single value 'local')")
    if dc.shards < 0:
        raise ConfigError(
            f"data_service_shards must be >= 0 (0 = one per reader), "
            f"got {dc.shards}")
    if dc.cache_batches < 1:
        raise ConfigError(
            f"data_service_cache must be >= 1, got {dc.cache_batches}")
    if dc.readahead < 0:
        raise ConfigError(
            f"data_service_readahead must be >= 0, got {dc.readahead}")
    if dc.prefetch < 0:
        raise ConfigError(
            f"data_service_prefetch must be >= 0, got {dc.prefetch}")
    if dc.timeout_ms <= 0:
        raise ConfigError(
            f"data_service_timeout_ms must be > 0, got "
            f"{dc.timeout_ms}")
    if dc.local_fallback not in (0, 1):
        raise ConfigError(
            f"data_service_local_fallback must be 0 or 1, got "
            f"{dc.local_fallback}")
    if dc.reader < -1:
        raise ConfigError(
            f"data_service_reader must be >= 0 (or -1 = unset), got "
            f"{dc.reader}")
    if dc.enabled and dc.local_only and dc.shards < 1:
        raise ConfigError(
            "data_service = local needs an explicit "
            "data_service_shards >= 1 (there is no endpoint list to "
            "default the shard count from)")
    return dc


# -- model health -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """The ``health_*`` knob set (doc/tasks.md "Model health"). One
    validated namespace, same contract as ``serve_*`` / ``telemetry_*``:
    a typo'd key raises instead of silently training unobserved.
    ``health = 1`` makes the train step compute compact per-layer
    numerics IN-TRACE (grad RMS/abs-max/finite-fraction, param RMS,
    update-to-weight ratio, activation abs-max / dead-ReLU fraction /
    BN batch-variance floor) that ride the step outputs and host-sync
    only every ``health_interval`` steps; ``health = 0`` (default) adds
    ZERO ops to the jaxpr and zero host syncs — the off path is
    byte-identical to a build that never heard of this namespace
    (pinned by tests/test_modelhealth.py)."""
    enabled: int = 0        # health: 1 = in-step model-health probe
    interval: int = 0       # health_interval: sync cadence in steps
    #                         (0 = follow sentinel_interval, default 8)
    window: int = 3         # health_window: consecutive bad syncs
    #                         before a detector emits health_advice
    dead_frac: float = 0.9  # health_dead_frac: dead-ReLU threshold
    bn_var_floor: float = 1e-8  # health_bn_var_floor: BN collapse
    ratio_min: float = 1e-8     # health_ratio_min: update/weight band
    ratio_max: float = 0.1      # health_ratio_max: update/weight band


def parse_health_config(cfg: ConfigPairs) -> HealthConfig:
    """Collect/validate the ``health`` / ``health_*`` keys (last
    occurrence wins; unknown keys in the namespace fail fast)."""
    known = {
        "health": ("enabled", int),
        "health_interval": ("interval", int),
        "health_window": ("window", int),
        "health_dead_frac": ("dead_frac", float),
        "health_bn_var_floor": ("bn_var_floor", float),
        "health_ratio_min": ("ratio_min", float),
        "health_ratio_max": ("ratio_max", float),
    }
    vals = {}
    for name, val in cfg:
        if name == "health" or name.startswith("health_"):
            if name not in known:
                raise ConfigError(
                    f"unknown health setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            field, conv = known[name]
            try:
                vals[field] = conv(val)
            except ValueError as e:
                raise ConfigError(f"bad {name} value {val!r}: {e}")
    hc = HealthConfig(**vals)
    if hc.enabled not in (0, 1):
        raise ConfigError(f"health must be 0 or 1, got {hc.enabled}")
    if hc.interval < 0:
        raise ConfigError(
            f"health_interval must be >= 0 (0 = sentinel_interval), "
            f"got {hc.interval}")
    if hc.window < 1:
        raise ConfigError(
            f"health_window must be >= 1, got {hc.window}")
    if not 0.0 < hc.dead_frac <= 1.0:
        raise ConfigError(
            f"health_dead_frac must be in (0, 1], got {hc.dead_frac}")
    if hc.bn_var_floor < 0:
        raise ConfigError(
            f"health_bn_var_floor must be >= 0, got {hc.bn_var_floor}")
    if not 0.0 <= hc.ratio_min < hc.ratio_max:
        raise ConfigError(
            "health_ratio_min must be >= 0 and < health_ratio_max, got "
            f"{hc.ratio_min}/{hc.ratio_max}")
    return hc


# -- IO retry policy ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Backoff knobs for transient-IO retry (resilience.retry_call),
    applied by io/stream.py to every remote operation. Defaults: 4
    attempts, 50 ms -> 2 s full-jitter exponential backoff."""
    attempts: int = 4
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    jitter: float = 1.0          # 0 = deterministic backoff, 1 = full jitter


def parse_retry_policy(cfg: ConfigPairs) -> RetryPolicy:
    """Build a :class:`RetryPolicy` from ``io_retry_attempts`` /
    ``io_retry_base_ms`` / ``io_retry_max_ms`` / ``io_retry_jitter``
    config keys (last occurrence wins, like every global key)."""
    known = {"io_retry_attempts", "io_retry_base_ms", "io_retry_max_ms",
             "io_retry_jitter"}
    vals = {}
    for name, val in cfg:
        if name.startswith("io_retry_"):
            if name not in known:
                # a typo'd retry knob silently falling back to defaults
                # is exactly the kind of quiet misconfiguration this
                # namespace check is cheap insurance against
                raise ConfigError(
                    f"unknown retry setting {name!r}; valid keys: "
                    + ", ".join(sorted(known)))
            vals[name] = val
    try:
        pol = RetryPolicy(
            attempts=int(vals.get("io_retry_attempts", "4")),
            base_delay_s=float(vals.get("io_retry_base_ms", "50")) / 1e3,
            max_delay_s=float(vals.get("io_retry_max_ms", "2000")) / 1e3,
            jitter=float(vals.get("io_retry_jitter", "1.0")))
    except ValueError as e:
        raise ConfigError(f"bad io_retry_* value: {e}")
    if pol.attempts < 1:
        raise ConfigError(
            f"io_retry_attempts must be >= 1, got {pol.attempts}")
    if not 0.0 <= pol.jitter <= 1.0:
        raise ConfigError(
            f"io_retry_jitter must be in [0, 1], got {pol.jitter}")
    return pol


def parse_policy(name: str) -> Policy:
    """``compute_dtype`` config value -> :class:`Policy` (fp32 masters and
    outputs, the named compute dtype in between)."""
    import jax.numpy as jnp
    canon = _DTYPE_NAMES.get(name.strip().lower())
    if canon is None:
        raise ConfigError(
            f"compute_dtype must be one of float32|bfloat16|float16 "
            f"(got {name!r})")
    compute = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
               "float16": jnp.float16}[canon]
    return Policy(param_dtype=jnp.float32, compute_dtype=compute,
                  output_dtype=jnp.float32)


class _Tokenizer:
    def __init__(self, text: str):
        self._text = text
        self._pos = 0
        self._line = 1

    def _getc(self) -> str:
        if self._pos >= len(self._text):
            return ""
        ch = self._text[self._pos]
        self._pos += 1
        if ch == "\n":
            self._line += 1
        return ch

    def tokens(self) -> Iterator[str]:
        """Yield raw tokens; ``=`` is always its own token."""
        ch = self._getc()
        tok: List[str] = []
        while ch:
            if ch == "#":
                while ch and ch not in "\r\n":
                    ch = self._getc()
                continue
            if ch in ('"', "'"):
                if tok:
                    raise ConfigError(
                        f"line {self._line}: token followed directly by string")
                quote = ch
                buf: List[str] = []
                ch = self._getc()
                while True:
                    if not ch:
                        raise ConfigError(f"line {self._line}: unterminated string")
                    if ch == "\\":
                        buf.append(self._getc())
                    elif ch == quote:
                        break
                    elif ch in "\r\n" and quote == '"':
                        raise ConfigError(f"line {self._line}: unterminated string")
                    else:
                        buf.append(ch)
                    ch = self._getc()
                yield "".join(buf)
                ch = self._getc()
                continue
            if ch == "=":
                if tok:
                    yield "".join(tok)
                    tok = []
                yield "="
                ch = self._getc()
                continue
            if ch in " \t\r\n":
                if tok:
                    yield "".join(tok)
                    tok = []
                ch = self._getc()
                continue
            tok.append(ch)
            ch = self._getc()
        if tok:
            yield "".join(tok)


def parse_config_string(text: str) -> ConfigPairs:
    """Parse config text into an ordered list of (name, value) pairs."""
    out: ConfigPairs = []
    toks = list(_Tokenizer(text).tokens())
    i = 0
    while i < len(toks):
        name = toks[i]
        if name == "=":
            raise ConfigError("expected parameter name, got '='")
        if i + 1 >= len(toks):
            raise ConfigError(f"dangling token {name!r} at end of config")
        if toks[i + 1] != "=":
            raise ConfigError(f"expected '=' after {name!r}")
        if i + 2 >= len(toks) or toks[i + 2] == "=":
            raise ConfigError(f"expected value after '{name} ='")
        out.append((name, toks[i + 2]))
        i += 3
    return out


def parse_config_file(path: str) -> ConfigPairs:
    from .io.stream import sopen
    with sopen(path, "rb") as f:
        return parse_config_string(f.read().decode("utf-8"))


def parse_cli_overrides(argv: List[str]) -> ConfigPairs:
    """Parse ``key=value`` command-line override arguments.

    Mirrors the reference CLI behavior (cxxnet_main.cpp:93-108): every arg
    containing ``=`` is appended after the config-file pairs so it wins for
    scalar settings.
    """
    out: ConfigPairs = []
    for arg in argv:
        if "=" not in arg:
            raise ConfigError(f"cannot parse CLI override {arg!r}; expected key=value")
        k, v = arg.split("=", 1)
        out.append((k.strip(), v.strip()))
    return out
