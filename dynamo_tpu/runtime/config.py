"""Layered runtime configuration: defaults <- TOML file <- DYN_* env vars.

Role-equivalent of the reference's Figment-based RuntimeConfig/WorkerConfig
(lib/runtime/src/config.rs:30-130).
"""

from __future__ import annotations

import os
import tomllib
from dataclasses import dataclass, field, fields
from typing import Any, Optional


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    return os.environ.get(name, default)


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v is not None else default


def jax_cache_dir() -> str:
    """The persistent XLA compilation cache directory of every JAX process
    of this program (server, graph workers, bench, smoke, prebake, tests):
    `JAX_COMPILATION_CACHE_DIR` where the environment sets it, else
    `<checkout>/.jax_cache`. The path is part of the cache key, so one
    fixed default is what makes a second start a hit."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def setup_jax_compilation_cache() -> str:
    """Turn the persistent compilation cache on at `jax_cache_dir()`, so a
    restarted process skips the cold compile of the engine's program set.
    Where `JAX_COMPILATION_CACHE_DIR` is set jax has already read it and
    this sets no other directory. Idempotent; returns the directory."""
    import jax

    cache_dir = jax_cache_dir()
    if jax.config.jax_compilation_cache_dir != cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program: the engine compiles few, large programs, so
    # there is no small-entry flood to guard against
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A Mosaic kernel's serialized body carries the source location of every
    # frame above it and is part of the cache key: with full tracebacks, an
    # edit that moves a line anywhere on the call path made every program
    # holding a Pallas kernel a cold compile (minutes at 7B; PERF.md PR 21).
    # One frame — the kernel's own line — keeps the key to what was compiled.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return cache_dir


@dataclass
class RuntimeConfig:
    """Per-process runtime settings.

    Environment overrides (highest precedence):
      DYN_FABRIC_ADDR       host:port of the fabric server ("" => in-process)
      DYN_TCP_HOST          advertised host for the TCP response plane
      DYN_TCP_PORT          fixed port for the TCP response plane (0 = ephemeral)
      DYN_RUNTIME_HTTP_ENABLED / DYN_RUNTIME_HTTP_PORT  system health/metrics server
      DYN_LEASE_TTL_S       discovery lease TTL seconds
      DYN_NAMESPACE         default namespace
      DYN_DEGRADED_MAX_S    control-plane blackout budget: how long the
                            data plane keeps serving (degraded, publishes
                            buffered) with the fabric unreachable before
                            workers self-fence / clients close streams
      DYN_WARM_RESTART_DIR  checkpoint dir for warm restarts: SIGTERM
                            drain writes the KV offload tiers + prefix
                            index as checksummed KVB2 pages; boot
                            restores them so restarts rejoin warm
    """

    fabric_addr: str = ""
    tcp_host: str = "127.0.0.1"
    tcp_port: int = 0
    http_enabled: bool = False
    http_port: int = 9090
    lease_ttl_s: float = 10.0
    namespace: str = "dynamo"

    @classmethod
    def from_settings(cls, config_path: Optional[str] = None) -> "RuntimeConfig":
        values: dict[str, Any] = {}
        path = config_path or _env("DYN_RUNTIME_CONFIG")
        if path and os.path.exists(path):
            with open(path, "rb") as f:
                doc = tomllib.load(f)
            section = doc.get("runtime", doc)
            known = {f.name for f in fields(cls)}
            values.update({k: v for k, v in section.items() if k in known})
        cfg = cls(**values)
        cfg.fabric_addr = _env("DYN_FABRIC_ADDR", cfg.fabric_addr) or ""
        cfg.tcp_host = _env("DYN_TCP_HOST", cfg.tcp_host) or cfg.tcp_host
        cfg.tcp_port = _env_int("DYN_TCP_PORT", cfg.tcp_port)
        cfg.http_enabled = _env_bool("DYN_RUNTIME_HTTP_ENABLED", cfg.http_enabled)
        cfg.http_port = _env_int("DYN_RUNTIME_HTTP_PORT", cfg.http_port)
        ttl = _env("DYN_LEASE_TTL_S")
        if ttl is not None:
            cfg.lease_ttl_s = float(ttl)
        cfg.namespace = _env("DYN_NAMESPACE", cfg.namespace) or cfg.namespace
        return cfg
