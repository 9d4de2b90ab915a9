"""Persistent XLA compile cache (doc/tasks.md "Sharded checkpointing").

Restart latency is the scale tax ROADMAP item 4 names: an elastic
takeover, a serve replica cold-start, or a plain resume pays checkpoint
restore PLUS a full recompile of every step/eval/serve executable. The
restore half is what the shard sets fix; this module removes the
recompile half by turning on JAX's persistent compilation cache — the
second process of a warm restart loads serialized executables instead
of re-running XLA.

Where the cache lives (:func:`resolve_cache_dir`) — one rule for every
entry point (main.py, bench.py, chip_smoke.py), because the directory
is part of what makes a later process find an earlier one's entries:

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other is
  ever set in code — whoever runs the program (a chip tool, a cluster
  launcher) can place the cache from outside. A config
  ``compile_cache_dir`` that names a different directory is an error.
* not set: the config's ``compile_cache_dir`` if it gives one, else
  the fixed ``<checkout>/.jax_cache`` (git-ignored) — never a path made
  from ``tempfile``, a pid or the time, which no second run would hit.
  (On the CPU backend that default stays off: see
  :func:`enable_compile_cache`.)

Observability (the ``cxxnet_compile_cache`` tag): enabling lands a
``compile_cache`` ledger event and a ``cxxnet_compile_cache_info{dir}``
info-gauge; every persistent-cache hit counts into
``cxxnet_compile_cache_hits_total`` AND lands a
``compile_cache`` ledger event with ``hit=true`` (both from the
process's one compile instrument, ``telemetry.anomaly.
install_compile_counter``, which also marks the load's
``compile.backend`` span ``cached``). That pairing is what
lets the PR-7 recompile-storm detector's operator distinguish
cold-start from storm: real XLA builds for a window are (compile
events - cache-hit events): where the ``backend_compile`` duration
event wraps the cached path too, ``cxxnet_compiles_total`` alone
over-counts a warm restart, while the hits series climbing in lockstep
marks the burst as cache-served cold-start, not recompilation.
"""

from __future__ import annotations

import os
import threading

from .telemetry.ledger import LEDGER
from .telemetry.registry import REGISTRY

_LOCK = threading.Lock()
_ENABLED_DIR = ""


#: what the environment may set to place the cache (JAX's own variable)
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed default: ``.jax_cache`` beside the package, in the checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def resolve_cache_dir(configured: str = "") -> str:
    """The one directory this process's compile cache may use (module
    docstring). ``configured``: the config's ``compile_cache_dir``."""
    env = os.environ.get(ENV_VAR, "")
    if env:
        if configured and os.path.abspath(configured) \
                != os.path.abspath(env):
            raise ValueError(
                f"compile_cache_dir = {configured} disagrees with "
                f"{ENV_VAR}={env}: the environment places the cache, "
                "drop the config key or make them agree")
        return os.path.abspath(env)
    return os.path.abspath(configured or DEFAULT_DIR)


def enable_compile_cache(configured: str = "", silent: bool = True) -> str:
    """Turn on JAX's persistent compilation cache in the directory
    :func:`resolve_cache_dir` names, before the first compile, and
    install the cache-hit counter. Idempotent. Returns the directory —
    or '' on the CPU backend when neither the environment nor the
    config placed a cache: XLA:CPU compiles in seconds, its loader
    logs a screenful for every cached executable it reads back, and
    the default directory exists to save a chip's compile minutes."""
    global _ENABLED_DIR
    import jax
    cache_dir = resolve_cache_dir(configured)
    if not (configured or os.environ.get(ENV_VAR)) \
            and jax.default_backend() == "cpu":
        return ""
    with _LOCK:
        previous = _ENABLED_DIR
    if previous == cache_dir:
        return cache_dir
    if previous:
        # JAX binds its cache object to a directory at first use: a
        # later process-local move (tests, embedders) must drop it
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache EVERY executable: the default min-compile-time gate
    # (1s) would skip exactly the many small serve-bucket / eval
    # executables whose recompile storm the detector measures
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _LOCK:
        _ENABLED_DIR = cache_dir
    from .telemetry.anomaly import install_compile_counter
    installed = install_compile_counter()
    REGISTRY.gauge(
        "cxxnet_compile_cache_info",
        "Persistent compile cache identity (constant 1)",
        labels=("dir",)).labels(cache_dir).set(1)
    LEDGER.event("compile_cache", dir=cache_dir, enabled=True,
                 hit_counter=installed)
    if not silent:
        print(f"compile cache: persistent executables in {cache_dir}",
              flush=True)
    return cache_dir


def cache_dir() -> str:
    """The enabled cache directory ('' when off)."""
    with _LOCK:
        return _ENABLED_DIR

