"""Layered runtime configuration.

Precedence (low to high): dataclass defaults < YAML file at ``DYN_CONFIG`` <
``DYN_*`` environment variables. Mirrors the reference's figment-based
RuntimeConfig (lib/runtime/src/config.rs:75, env prefixes at :219-265).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import yaml

_PREFIX = "DYN_"


def _coerce(value: str, typ: Any) -> Any:
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    return value


@dataclass
class RuntimeConfig:
    """Process-level runtime knobs (env prefix ``DYN_``)."""

    # identity / cluster
    namespace: str = "dynamo"
    hub_address: str = ""  # "host:port" of the hub service; empty = in-memory
    # replicated hub: comma-separated replica addresses (DYN_HUB_ADDRESSES);
    # takes precedence over hub_address — clients fail over across the list
    hub_addresses: str = ""
    static: bool = False  # static mode: no discovery, fixed peers (ref lib.rs:205)

    # data plane
    host: str = "127.0.0.1"  # address workers advertise for their TCP listener
    request_timeout_s: float = 600.0
    connect_timeout_s: float = 5.0
    # pre-dial worker channels on instance discovery (DYN_PREWARM_DIALS):
    # the first request to a fresh worker doesn't pay the TCP dial
    prewarm_dials: bool = True
    # directory for workers' unix-socket listeners (DYN_UDS_DIR): when set,
    # each EndpointServer also listens on a socket there and co-located
    # clients dial it instead of TCP; empty = TCP only. Coalescing/corking
    # knobs (DYN_STREAM_COALESCE / DYN_STREAM_CORK) live in transport.py.
    uds_dir: str = ""

    # leases / health
    lease_ttl_s: float = 10.0
    keepalive_interval_s: float = 3.0
    health_check_interval_s: float = 30.0
    health_check_timeout_s: float = 10.0
    # graceful drain (worker SIGTERM / k8s preStop): max seconds to let
    # in-flight requests finish before force-cancelling and exiting; keep
    # terminationGracePeriodSeconds comfortably above this
    drain_timeout_s: float = 30.0
    # per-endpoint withdrawal grace (DYN_WITHDRAW_GRACE_S): after the
    # instance key is deleted, the handler keeps serving this long so a
    # router that picked inside the watch-propagation window still lands
    # on a live worker instead of a corpse (scale-down drain contract).
    # Default covers in-process/LAN watch propagation; raise it on
    # clusters where router watch fan-out takes longer than this.
    withdraw_grace_s: float = 0.01

    # http frontend
    http_port: int = 8000
    system_port: int = 9090  # liveness/readiness/metrics server

    # logging
    log_level: str = "INFO"
    log_jsonl: bool = False

    # engine-side compute
    block_size: int = 64  # KV cache block granularity (tokens/block)
    # speculative decoding defaults for engine workers (DYN_SPEC_MODE /
    # DYN_SPEC_K_MAX; engine/spec.py): explicit --spec CLI flags win,
    # empty/0 falls through to the EngineConfig defaults ("off" / 8)
    spec_mode: str = ""
    spec_k_max: int = 0
    # guided decoding default for engine workers (DYN_GUIDED_MODE;
    # guided/): explicit --guided CLI flags win, empty falls through to
    # the EngineConfig default ("auto")
    guided_mode: str = ""
    # per-tenant fairness quotas for engine workers (DYN_TENANT_QUOTAS;
    # engine/tenancy.py grammar:
    # "tenantA:weight=4,rate=1000,burst=2000;*:rate=200"). Explicit
    # --tenant-quotas CLI flags win; empty = unmetered equal weights.
    tenant_quotas: str = ""

    extra: dict[str, Any] = field(default_factory=dict)

    def hub_target(self) -> str:
        """The address string to hand connect_hub: the replica list when
        configured, else the single hub address (possibly empty =
        in-memory)."""
        return self.hub_addresses or self.hub_address

    def override_hub(self, address: str) -> "RuntimeConfig":
        """CLI ``--hub`` beats env: route hub_target() at ``address``
        (single ``host:port`` or a comma-separated replica list). One
        helper so every entry point applies the same precedence."""
        self.hub_address = self.hub_addresses = address
        return self

    @classmethod
    def from_env(cls, env: dict[str, str] | None = None) -> "RuntimeConfig":
        env = dict(os.environ if env is None else env)
        layers: dict[str, Any] = {}

        cfg_path = env.get(_PREFIX + "CONFIG")
        if cfg_path and Path(cfg_path).exists():
            loaded = yaml.safe_load(Path(cfg_path).read_text()) or {}
            if not isinstance(loaded, dict):
                raise ValueError(f"config file {cfg_path} must be a mapping")
            layers.update(loaded)

        fields = {f.name: f for f in dataclasses.fields(cls)}
        for key, raw in env.items():
            if not key.startswith(_PREFIX):
                continue
            name = key[len(_PREFIX) :].lower()
            if name != "extra" and name != "config":
                layers[name] = raw  # known keys coerced below via default's type

        known = {k: v for k, v in layers.items() if k in fields and k != "extra"}
        extra = {k: v for k, v in layers.items() if k not in fields}
        # dataclasses stores declared types as strings under future annotations;
        # coerce via the default value's type instead.
        defaults = cls()
        for k, v in list(known.items()):
            if isinstance(v, str):
                known[k] = _coerce(v, type(getattr(defaults, k)))
        return cls(**known, extra=extra)


def config_from_env() -> RuntimeConfig:
    return RuntimeConfig.from_env()
