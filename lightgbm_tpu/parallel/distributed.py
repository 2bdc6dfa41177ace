"""Multi-host bring-up: the reference's socket machine-list handshake mapped
onto jax.distributed.

Reference: src/network/linkers_socket.cpp (Linkers::Construct — parse
machine list, rank by matching the local address, TCP handshake) and
include/LightGBM/network.h.  The TPU-native replacement: every process calls
`jax.distributed.initialize` against a coordinator (machine 0); afterwards
`jax.devices()` is the GLOBAL device list across hosts and the existing
`jax.sharding.Mesh` + shard_map learners run unchanged — XLA routes
collectives over ICI within a slice and DCN across hosts, replacing the
reference's hand-rolled Allreduce/ReduceScatter over TCP.

Config mapping (reference: Config network params):
  machines / machine_list_filename : "host:port" entries, one per process;
    entry 0 is the coordinator
  num_machines                     : process count (must match entries)
  local_listen_port                : used to disambiguate rank when several
    processes share one host (host:port matching, like the reference)
  time_out (minutes)               : initialization timeout

Rank detection mirrors the reference's Linkers::Construct: the local rank is
the machine-list entry whose host is a local address AND whose port equals
local_listen_port; the LIGHTGBM_TPU_RANK env var overrides (for containers
whose local addresses are not in the list).
"""

from __future__ import annotations

import os
import socket
import time
from typing import List, Tuple

from ..utils.log import log_info, log_warning

_initialized = False


def _parse_machines(cfg) -> List[Tuple[str, int]]:
    raw = cfg.machines
    if not raw and cfg.machine_list_filename:
        lines = []
        with open(cfg.machine_list_filename) as fh:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                # reference format: "host port" (Common::Split drops repeats)
                lines.append(":".join(line.split()))
        raw = ",".join(lines)
    out = []
    for entry in raw.replace("\n", ",").split(","):
        entry = entry.strip()
        if not entry:
            continue
        host, _, port = entry.partition(":")
        out.append((host, int(port) if port else cfg.local_listen_port))
    return out


def _local_addresses() -> set:
    names = {"localhost", "127.0.0.1", socket.gethostname()}
    try:
        names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    return names


def detect_rank(cfg, machines: List[Tuple[str, int]]) -> int:
    env = os.environ.get("LIGHTGBM_TPU_RANK")
    if env is not None:
        return int(env)
    local = _local_addresses()
    for i, (host, port) in enumerate(machines):
        if host in local and port == cfg.local_listen_port:
            return i
    # host-only fallback is safe only when it is unambiguous (the reference
    # reports a port mismatch when several entries share this host)
    host_matches = [i for i, (host, _) in enumerate(machines) if host in local]
    if len(host_matches) == 1:
        return host_matches[0]
    if len(host_matches) > 1:
        raise ValueError(
            f"{len(host_matches)} machine-list entries match this host but "
            f"none matches local_listen_port={cfg.local_listen_port}; set "
            "local_listen_port per process or LIGHTGBM_TPU_RANK"
        )
    raise ValueError(
        "cannot determine this machine's rank: no machine-list entry matches "
        f"a local address ({sorted(local)}); set LIGHTGBM_TPU_RANK"
    )


def init_distributed(cfg) -> bool:
    """Bring up the multi-process JAX runtime from the reference's network
    params.  Returns True when a multi-host runtime is (already) active.
    Idempotent; a no-op for num_machines <= 1."""
    global _initialized
    if cfg.num_machines <= 1:
        return False
    if _initialized:
        return True
    import jax

    machines = _parse_machines(cfg)
    if len(machines) != cfg.num_machines:
        raise ValueError(
            f"num_machines={cfg.num_machines} but the machine list has "
            f"{len(machines)} entries"
        )
    rank = detect_rank(cfg, machines)
    host0, port0 = machines[0]
    coordinator = f"{host0}:{port0}"
    log_info(
        f"Initializing distributed runtime: rank {rank}/{cfg.num_machines}, "
        f"coordinator {coordinator}"
    )
    # bounded retry-with-backoff for the rendezvous phase: coordinator
    # bring-up races (rank 0 not listening yet, stale TIME_WAIT sockets,
    # transient DNS) are the dominant init failure class on real fleets
    # and are safe to retry — jax.distributed.initialize is all-or-nothing
    # before it succeeds (docs/ROBUSTNESS.md).  LGBMTPU_INIT_RETRIES=1
    # disables retries.
    attempts = max(int(os.environ.get("LGBMTPU_INIT_RETRIES", "3")), 1)
    init_timeout = max(cfg.time_out, 1) * 60
    for attempt in range(attempts):
        t0 = time.monotonic()
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=cfg.num_machines,
                process_id=rank,
                initialization_timeout=init_timeout,
            )
            break
        except (ValueError, TypeError):
            # bad address / bad config: deterministic, never retryable
            raise
        except Exception as e:  # noqa: BLE001 — last attempt re-raises
            # only FAST failures are the transient class worth retrying
            # (coordinator not listening yet, connection refused).  An
            # attempt that burned a large share of the rendezvous timeout
            # means every peer waited it out too — retrying would multiply
            # a multi-hour worst case instead of failing fast.
            elapsed = time.monotonic() - t0
            if attempt == attempts - 1 or elapsed >= 0.5 * init_timeout:
                raise
            delay = min(1.0 * (2 ** attempt), 15.0)
            log_warning(
                f"distributed init attempt {attempt + 1}/{attempts} failed "
                f"after {elapsed:.1f}s ({type(e).__name__}: {str(e)[:200]}); "
                f"retrying rendezvous in {delay:.1f}s")
            time.sleep(delay)
    _initialized = True
    log_info(
        f"Distributed runtime up: {jax.process_count()} processes, "
        f"{jax.device_count()} global devices"
    )
    return True
