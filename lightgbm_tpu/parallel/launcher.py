"""Multi-process training launcher — the Dask-orchestration analogue.

Reference: python-package/lightgbm/dask.py (~1,700 LoC): align partitions to
workers, find open ports, build the `machines` list, inject
num_machines/local_listen_port/tree_learner, run plain `lightgbm.train` on
every worker with network params, return the rank-0 model.

TPU-native redesign: workers are local processes wired through
`jax.distributed` (parallel/distributed.py maps the reference's machine-list
handshake onto the coordinator bring-up).  Each worker receives ONLY its row
shard (`pre_partition` semantics: bin boundaries sync from the global
sample, the global device array is assembled from process-local shards, and
no rank ever materializes the full dataset).  Every rank ends up with the
identical model; the launcher returns rank 0's.

eval_set support (reference: dask.py _train accepts eval_set and evaluates
per-worker): each eval set is row-sharded across ranks exactly like the
training data; workers build valid Datasets against the train shard's
binner and evaluate through the pre_partition synced metric path
(models/gbdt.py::_eval_at_synced — Network::GlobalSyncUpBySum analogue),
so every rank sees identical metric values and early stopping fires
identically everywhere.

This launcher is the single-host (loopback) form; on a real multi-host pod
run one worker per host with the same `machines` list — the worker body is
ordinary `lightgbm_tpu.train`, exactly like the reference's `_train_part`.
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import _ALIASES, Config
from ..obs import metrics as _obs
from ..obs import trace as _trace
from ..utils import checkpoint as _checkpoint
from ..utils.log import log_warning

_WORKER_SRC = r"""
import os, sys, json
sys.path.insert(0, os.environ["LGBM_TPU_REPO"])
import numpy as np
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.distributed import init_distributed

shard = np.load(os.environ["LGBM_TPU_SHARD"], allow_pickle=True)
net = {k: shard[k].item() for k in ("num_machines", "machines",
                                    "local_listen_port", "time_out")}
rank = os.environ["LIGHTGBM_TPU_RANK"]
# multi-slice fleets (docs/ROBUSTNESS.md "Slice-granular recovery"): the
# rendezvous rank is slice-LOCAL (each slice is its own collective
# world) while the worker id is fleet-GLOBAL — model outputs, acks and
# shard fingerprints key on the global id
wid = os.environ.get("LGBM_TPU_WORKER_ID", rank)

# per-rank metrics flight recorder (docs/OBSERVABILITY.md "Fleet
# metrics"): atomic snapshot writes start BEFORE the rendezvous and
# repeat every period, so even a rank that dies mid-round leaves a
# mergeable file for the launcher's fleet_metrics.json
from lightgbm_tpu.obs import metrics as _obs_metrics

_snap_path = os.environ.get("LGBMTPU_METRICS_SNAPSHOT_FILE")
if _snap_path:
    _obs_metrics.start_periodic_snapshots(
        _snap_path,
        float(os.environ.get("LGBMTPU_METRICS_SNAPSHOT_PERIOD_S", "1.0")))

if int(net["num_machines"]) > 1:
    # a 1-rank fleet skips the multi-process runtime entirely (the
    # simulated-rank recovery tests drive every launcher/checkpoint path
    # this way on containers whose jax lacks multiproc collectives)
    assert init_distributed(Config.from_dict(net))

import lightgbm_tpu as lgb

params = dict(np.load(os.environ["LGBM_TPU_PARAMS"], allow_pickle=True)[
    "params"].item())
params.update(net)
params["pre_partition"] = int(net["num_machines"]) > 1
if int(net["num_machines"]) > 1:
    params.setdefault("tree_learner", "data")
_cache = os.environ.get("LGBM_TPU_CACHE")
if _cache:
    # rank-sharded cache feed (docs/DISTRIBUTED.md): this worker reads
    # ONLY its row shard of one shared save_binary cache through
    # BinCacheStream(shard=) — ingest scales with the fleet instead of
    # every rank decompressing the full matrix
    _lo, _hi, _pad = (int(t) for t in
                      os.environ["LGBM_TPU_CACHE_SHARD"].split(","))
    ds = lgb.Dataset(
        _cache, params=dict(params, bin_cache_shard=(_lo, _hi, _pad)))
else:
    ds = lgb.Dataset(
        shard["X"],
        label=shard["y"],
        weight=(shard["w"] if shard["w"].size > 0 else None),
        group=(shard["g"] if "g" in shard and shard["g"].size > 0 else None),
    )
valid_sets, valid_names = [], []
n_eval = int(shard["n_eval"].item()) if "n_eval" in shard else 0
for i in range(n_eval):
    valid_sets.append(lgb.Dataset(
        shard[f"ev{i}_X"],
        label=shard[f"ev{i}_y"],
        weight=(shard[f"ev{i}_w"] if shard[f"ev{i}_w"].size > 0 else None),
        group=(shard[f"ev{i}_g"] if shard[f"ev{i}_g"].size > 0 else None),
        reference=ds,
    ))
    valid_names.append(str(shard[f"ev{i}_name"].item()))
callbacks = []
evals_result = {}
es_rounds = int(os.environ.get("LGBM_TPU_ES_ROUNDS", "0"))
if es_rounds > 0 and valid_sets:
    callbacks.append(lgb.early_stopping(es_rounds, verbose=False))
if valid_sets:
    callbacks.append(lgb.record_evaluation(evals_result))
# coordinated fleet checkpoints (docs/ROBUSTNESS.md "Elastic fleet
# recovery"): every ckpt_freq GLOBAL iterations rank 0 writes the
# fleet snapshot + manifest through utils/checkpoint.py and every other
# rank drops its sha-carrying ack — the round becomes resumable only
# once ALL ranks confirmed, so a crash anywhere in the window leaves the
# previous fleet-valid round authoritative
_ckpt_dir = os.environ.get("LGBMTPU_FLEET_CKPT_DIR")
_ckpt_freq = int(os.environ.get("LGBMTPU_FLEET_SNAPSHOT_FREQ", "0") or 0)
_ckpt_on = bool(_ckpt_dir) and _ckpt_freq > 0
if _ckpt_on:
    from lightgbm_tpu.utils import checkpoint as _ckpt

    _world = int(os.environ.get("LGBMTPU_FLEET_WORLD",
                                str(net["num_machines"])))
    _keep = int(os.environ.get("LGBMTPU_FLEET_SNAPSHOT_KEEP", "0") or 0)
    _rank_i = int(wid)  # manifest roles/acks key on the GLOBAL id
    _slices = json.loads(os.environ.get("LGBMTPU_FLEET_SLICES", "{}")) or None
    _shards = {}
    _shards_json = os.environ.get("LGBMTPU_FLEET_SHARDS_JSON")
    if _shards_json and os.path.exists(_shards_json):
        with open(_shards_json) as fh:
            _shards = json.load(fh)

    def _fleet_ckpt_cb(env):
        it = env.model.current_iteration()  # GLOBAL iteration: resumed
        if it % _ckpt_freq:                 # runs keep the numbering
            return
        text = env.model.model_to_string(raw_deltas=True)
        if _rank_i == 0:
            _ckpt.write_fleet_checkpoint(_ckpt_dir, text, it, _world,
                                         _shards, keep=_keep,
                                         slices=_slices)
        else:
            _ckpt.confirm_fleet_checkpoint(_ckpt_dir, it, _rank_i, text)
    _fleet_ckpt_cb.order = 100
    callbacks.append(_fleet_ckpt_cb)

if os.environ.get("LGBMTPU_FAULT"):
    # worker_death injection site (utils/faults.py): rank-gated hard exit
    # at the start of a chosen iteration — the scenario the launcher
    # watchdog exists to catch
    from lightgbm_tpu.utils import faults as _faults

    def _await_fleet_ack(done_round):
        # the injected death models "a rank is lost AFTER the fleet
        # confirmed the last checkpoint round it completed".  Slices are
        # independent rendezvous worlds, so on a loaded machine this rank
        # can reach its death round before rank 0 (another slice) has
        # published that round's manifest, and the replacement would then
        # resume from nothing.  Wait for the manifest the scenario
        # assumes: slice-valid without this slice's own acks.
        import time

        want = done_round // _ckpt_freq * _ckpt_freq if _ckpt_on else 0
        if want <= 0:
            return
        sl = _slices or {}
        mine = tuple(int(r) for r, s in sl.items() if s == sl.get(wid))
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            got = _ckpt.latest_slice_valid_fleet_manifest(
                _ckpt_dir, _world, mine)
            if got is not None and got[0] >= want:
                return
            time.sleep(0.05)

    def _fault_cb(env):
        if _faults.would_fire("worker_death", env.iteration + 1):
            _await_fleet_ack(env.iteration)
        _faults.maybe_crash("worker_death", env.iteration + 1)
    _fault_cb.before_iteration = True
    _fault_cb.order = -100
    callbacks.append(_fault_cb)

bst = lgb.train(params, ds, int(os.environ["LGBM_TPU_ROUNDS"]),
                valid_sets=valid_sets or None,
                valid_names=valid_names or None,
                callbacks=callbacks,
                # resume-to-round relaunch: the launcher hands a restarted
                # fleet the newest fleet-VALID manifest; engine.train
                # verifies it (incl. this rank's shard fingerprint) and
                # trains only the remaining rounds
                resume=os.environ.get("LGBMTPU_RESUME_MANIFEST"))
out = os.environ["LGBM_TPU_MODEL_OUT"]
bst.save_model(out + f".rank{wid}")
if wid == "0":
    meta = {"best_iteration": bst.best_iteration,
            "best_score": {d: dict(m) for d, m in bst.best_score.items()},
            "evals_result": {d: {k: list(map(float, v))
                                 for k, v in m.items()}
                             for d, m in evals_result.items()}}
    with open(out + ".meta.json", "w") as fh:
        json.dump(meta, fh)
if _snap_path:
    # stop the writer and flush one exact final snapshot — a clean exit's
    # fleet entry must not be a period stale
    _obs_metrics.stop_periodic_snapshots()
print("LAUNCHER_RANK_OK", wid, flush=True)
"""


# the most recent train_distributed launch directory — lets callers and
# tests locate fleet_events.jsonl / fleet_metrics.json after a FAILED
# launch too (the success path exposes them on the returned booster)
_LAST_LAUNCH_DIR: Optional[str] = None


class WorkerFailure(RuntimeError):
    """A launcher worker died (non-zero exit), HUNG (heartbeat went stale
    past the timeout), or the launch timed out.  Carries the failing rank
    (or None for timeouts) so retry logic and tests can tell the cases
    apart.  ``slice_id`` is set when the failure was handled
    slice-granularly (docs/ROBUSTNESS.md "Slice-granular recovery"):
    only that slice's process group was killed, the survivors are STILL
    RUNNING, and the caller owns respawning the slice."""

    def __init__(self, msg: str, rank: Optional[int] = None,
                 timed_out: bool = False, hung: bool = False,
                 slice_id: Optional[int] = None):
        super().__init__(msg)
        self.rank = rank
        self.timed_out = timed_out
        self.hung = hung
        self.slice_id = slice_id


def _kill_worker_group(proc: subprocess.Popen) -> None:
    """Kill a worker AND everything it spawned (each worker is started in
    its own session, so its process group is exactly its subtree) — no
    zombies may outlive a failed launch."""
    try:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            proc.kill()
        except OSError:
            pass
    try:
        proc.wait(timeout=5)
    except subprocess.TimeoutExpired:
        pass


def _log_tail(log_path: str, nbytes: int = 2000) -> str:
    try:
        with open(log_path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            size = fh.tell()
            fh.seek(max(0, size - nbytes))
            return fh.read().decode(errors="replace")
    except OSError as e:
        return f"<log unreadable: {e}>"


def _read_heartbeat(snap_path: Optional[str]) -> Optional[float]:
    """The ``heartbeat_ts`` gauge from a per-rank metrics snapshot file
    (the atomic JSON the worker's periodic writer keeps), or None while
    the rank has not started training / written a snapshot yet — or has
    RETIRED its heartbeat (``heartbeat_done``, set by engine.train's
    finally): the post-training tail (model save, final eval, fleet ack)
    may legitimately exceed the hang timeout and must not read as a
    stalled round loop."""
    if not snap_path:
        return None
    try:
        with open(snap_path, encoding="utf-8") as fh:
            snap = json.load(fh)
        gauges = snap.get("gauges", {})
        if gauges.get("heartbeat_done"):
            return None
        hb = gauges.get("heartbeat_ts")
        return float(hb) if hb is not None else None
    except (OSError, ValueError, AttributeError):
        return None  # missing/partial file: not a heartbeat signal yet


_SLOW_RANK_FLOOR_S = 1.0  # hard minimum for the slow-rank age floor; the
# effective floor adds headroom for the snapshot period + read cadence
# (see _watch_workers) so write/read phase aliasing can't false-positive


def _snapshot_period() -> float:
    """The workers' periodic metrics-snapshot period (the granularity at
    which heartbeat values can possibly change on disk)."""
    try:
        return float(os.environ.get(
            "LGBMTPU_METRICS_SNAPSHOT_PERIOD_S", "1.0"))
    except ValueError:
        return 1.0


def _watch_workers(workers, timeout_s: float,
                   poll_interval: float = 0.1,
                   heartbeat_timeout_s: Optional[float] = None,
                   heartbeat_paths: Optional[Dict[int, str]] = None,
                   slow_rank_factor: float = 0.0,
                   hb_ages: Optional[Dict[int, float]] = None,
                   slice_of: Optional[Dict[int, int]] = None,
                   slice_granular: bool = False,
                   done: Optional[set] = None) -> None:
    """Per-worker liveness watchdog: poll + exit-code harvest, plus
    HEARTBEAT staleness (docs/ROBUSTNESS.md "Elastic fleet recovery").

    ``workers`` is a list of (rank, Popen, log_path).  Returns when every
    worker exits 0.  A worker exiting non-zero fails the run within
    ~poll_interval seconds — not after a ``communicate(timeout=600)``
    hang waiting on the survivors, which block forever on the dead
    rank's collectives — with that worker's log tail in the error.

    With ``heartbeat_timeout_s`` > 0 and per-rank snapshot paths, a rank
    whose ``heartbeat_ts`` gauge stops CHANGING for longer than the
    timeout is declared HUNG (the wedged-in-a-collective class an
    exit-code watchdog can never catch: the process is alive, its
    snapshot-writer daemon keeps the file fresh, but the main thread
    stopped making rounds).  Change-tracking — not file mtime, not clock
    comparison — is deliberate on both counts: the daemon writer keeps
    mtime moving during a hang, and the gauge is the WORKER's monotonic
    clock, incomparable across processes.  Staleness is armed per rank
    from its first observed heartbeat; rendezvous hangs before round 1
    stay covered by ``timeout_s``.  The hung rank's process group is
    killed and the failure routes into the restart path exactly as a
    death does.

    ``slow_rank_factor`` > 0 adds straggler DETECTION on the same
    heartbeat reads (nothing is killed): a rank whose heartbeat age
    exceeds factor x the fleet median (and a 1 s floor) emits one
    ``fleet_slow_rank`` event + ``fleet_slow_ranks_total`` bump per slow
    episode — the class where a rank still makes rounds but k x slower
    than its peers, which the full-stall watchdog can never see.  With
    ``slice_of`` the median is computed WITHIN each rank's slice, not
    fleet-wide: slices make rounds at different cadences (DCN phase
    skew, per-slice data skew), so one slow SLICE would otherwise drag
    the fleet median up and mask a genuine straggler rank inside
    another slice.  ``hb_ages``, when given, is kept updated with each
    rank's current heartbeat age — the launcher's live /metrics
    collector reads it for the per-rank ``fleet_heartbeat_age_s``
    labeled gauge.

    On failure or timeout the WHOLE process group of every worker is
    killed and every tail is harvested (docs/ROBUSTNESS.md) — UNLESS
    ``slice_granular`` is set and the failure is attributable to one
    rank's slice: then only THAT slice's process groups are killed, the
    raised :class:`WorkerFailure` carries ``slice_id``, and the
    surviving slices keep running for the caller to rejoin a
    replacement slice against (docs/ROBUSTNESS.md "Slice-granular
    recovery")."""
    deadline = time.monotonic() + timeout_s
    # `done` may be threaded across calls (the slice-respawn loop
    # re-enters this watch): a rank that already exited 0 must not
    # re-emit its worker_exit event into the fleet flight recorder
    done = set() if done is None else done

    def _scoped_failure(rank, msg, hung=False):
        """Kill the blast radius and build the failure: the failing
        rank's slice alone under slice-granular handling (survivors keep
        running), the cleanup handler's whole-fleet kill otherwise."""
        sid = (slice_of.get(rank) if slice_granular and slice_of else None)
        if sid is not None:
            for r2, p2, _ in workers:
                if slice_of.get(r2) == sid and p2.poll() is None:
                    _kill_worker_group(p2)
        return WorkerFailure(msg, rank=rank, hung=hung, slice_id=sid)
    # rank -> (value, t_change, changed_once): staleness is armed only
    # after the heartbeat has been seen to CHANGE (see below)
    hb_seen: Dict[int, Tuple[float, float, bool]] = {}
    hb_next = 0.0
    slow_active: set = set()  # ranks currently in a slow episode
    watch_hb = bool((heartbeat_timeout_s or slow_rank_factor
                     or hb_ages is not None) and heartbeat_paths)
    try:
        while len(done) < len(workers):
            for rank, proc, log_path in workers:
                if rank in done:
                    continue
                rc = proc.poll()
                if rc is None:
                    continue
                if rc == 0:
                    done.add(rank)
                    _obs.event("worker_exit", worker_rank=rank, exit_code=0)
                    continue
                _obs.counter("launcher_worker_deaths_total").inc()
                _obs.event("worker_death", worker_rank=rank, exit_code=rc,
                           log=log_path)
                raise _scoped_failure(
                    rank,
                    f"launcher worker rank {rank} died with exit code {rc}; "
                    f"its failure scope killed. Tail of rank {rank}'s log "
                    f"({log_path}):\n{_log_tail(log_path)}")
            now = time.monotonic()
            if watch_hb and now >= hb_next:
                # re-read the small per-rank JSONs at most ~1 Hz (and at
                # least 4x per timeout window), not per 0.1 s poll tick
                hb_next = now + (min(1.0, heartbeat_timeout_s / 4.0)
                                 if heartbeat_timeout_s else 1.0)
                stalest: Optional[Tuple[float, int, "subprocess.Popen", str]] = None
                ages: Dict[int, float] = {}  # armed ranks' heartbeat age
                for rank, proc, log_path in workers:
                    if rank in done or proc.poll() is not None:
                        if hb_ages is not None:
                            hb_ages.pop(rank, None)
                        continue
                    hb = _read_heartbeat(heartbeat_paths.get(rank))
                    if hb is None:
                        if hb_ages is not None:
                            hb_ages.pop(rank, None)  # retired/not started
                        continue
                    prev = hb_seen.get(rank)
                    if prev is None:
                        # first observation arms tracking only: round 1
                        # includes jit COMPILATION, which stalls the
                        # heartbeat for arbitrarily long without being a
                        # hang — staleness counts only once the value has
                        # been seen to CHANGE (round 2 onward); earlier
                        # hangs stay covered by the launch timeout
                        hb_seen[rank] = (hb, now, False)
                        continue
                    if hb != prev[0]:
                        hb_seen[rank] = (hb, now, True)
                        ages[rank] = 0.0
                        continue
                    if not prev[2]:
                        continue
                    stale = now - prev[1]
                    ages[rank] = stale
                    if heartbeat_timeout_s and stale > heartbeat_timeout_s \
                            and (stalest is None or stale > stalest[0]):
                        # a wedged collective stalls EVERY rank's
                        # heartbeat; blame the stalest rank — it stopped
                        # first, the rest are its victims
                        stalest = (stale, rank, proc, log_path)
                if hb_ages is not None:
                    hb_ages.update(ages)
                if slow_rank_factor and len(ages) >= 2:
                    # straggler detection on the SAME reads: slow = this
                    # rank's heartbeat age is factor x the median of its
                    # COMPARISON GROUP (and past the absolute floor — an
                    # idle fleet's read-phase jitter must not trip it).
                    # The group is the rank's SLICE when slice_of is
                    # given — slices make rounds at different cadences,
                    # so a slow slice would inflate a fleet-wide median
                    # and mask a straggler inside a healthy slice —
                    # else the whole fleet.  Emitted once per episode;
                    # the rank clears when it catches up.  LOWER-middle
                    # median: the upper pick would let one straggler
                    # inflate its own threshold — in a 2-rank group a
                    # 60x-slow rank would BE the "median" and never
                    # trip.  Floor sized over the snapshot-write period
                    # + the 1 Hz read cadence: a healthy rank whose
                    # write phase lands just after our read shows age
                    # ~(period + read tick) without being slow.
                    groups: Dict[Optional[int], list] = {}
                    for rank, age in ages.items():
                        gid = slice_of.get(rank) if slice_of else None
                        groups.setdefault(gid, []).append(age)
                    med_of = {
                        gid: sorted(v)[(len(v) - 1) // 2]
                        for gid, v in groups.items()}
                    slow_floor = max(_SLOW_RANK_FLOOR_S,
                                     2.0 * _snapshot_period() + 1.0)
                    for rank, age in ages.items():
                        gid = slice_of.get(rank) if slice_of else None
                        if len(groups[gid]) < 2:
                            continue  # a lone rank has no peer cadence
                        med = med_of[gid]
                        slow = age > max(slow_rank_factor * med, slow_floor)
                        if slow and rank not in slow_active:
                            slow_active.add(rank)
                            _obs.counter("fleet_slow_ranks_total").inc()
                            _obs.event(
                                "fleet_slow_rank", worker_rank=rank,
                                age_s=round(age, 3),
                                fleet_median_s=round(med, 3),
                                factor=slow_rank_factor,
                                slice=gid)
                        elif not slow:
                            slow_active.discard(rank)
                if stalest is not None:
                    stale, rank, proc, log_path = stalest
                    _obs.counter("fleet_hangs_total").inc()
                    _obs.event("worker_hang", worker_rank=rank,
                               stale_s=round(stale, 3),
                               heartbeat_timeout_s=heartbeat_timeout_s,
                               log=log_path)
                    _kill_worker_group(proc)
                    raise _scoped_failure(
                        rank,
                        f"launcher worker rank {rank} HUNG: heartbeat "
                        f"unchanged for {stale:.1f}s "
                        f"(> {heartbeat_timeout_s:g}s); process group "
                        f"killed. Tail of rank {rank}'s log "
                        f"({log_path}):\n{_log_tail(log_path)}",
                        hung=True)
            if time.monotonic() > deadline:
                _obs.counter("launcher_timeouts_total").inc()
                _obs.event("launch_timeout", timeout_s=timeout_s)
                tails = "\n".join(
                    f"--- rank {r} ({lp}) ---\n{_log_tail(lp)}"
                    for r, _, lp in workers)
                raise WorkerFailure(
                    f"launcher timed out after {timeout_s:.0f}s; all worker "
                    f"process groups killed. Worker log tails:\n{tails}",
                    timed_out=True)
            time.sleep(poll_interval)
    except BaseException as e:
        # single cleanup path for death, timeout, and anything else:
        # no code path may leak live workers — EXCEPT a slice-scoped
        # failure, whose whole point is that the surviving slices stay
        # up for the replacement slice to rejoin (the slice's own
        # process groups were already killed at the raise site)
        if not (isinstance(e, WorkerFailure) and e.slice_id is not None):
            for _, p2, _ in workers:
                if p2.poll() is None:
                    _kill_worker_group(p2)
        raise


def _fleet_live_collector(tmp: str, num_machines: int,
                          hb_ages: Dict[int, float],
                          slice_of: Optional[Dict[int, int]] = None):
    """Snapshot-time collector serving the LIVE fleet view from the
    launcher's own /metrics endpoint (docs/OBSERVABILITY.md "Fleet
    metrics"): every per-rank periodic snapshot file is merged in with
    ``rank="r"`` labels — while the workers are still RUNNING, not only
    in the at-exit fleet_metrics.json merge — plus each rank's current
    heartbeat age (``fleet_heartbeat_age_s{rank="r"}``) as the watchdog
    tracks it.  Registered per launch (same collector name: the next
    launch replaces it); pure host-side file reads, zero device work,
    and a torn mid-write file just skips one scrape (the worker's writes
    are atomic)."""
    def collect() -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {"counters": {}, "gauges": {}}
        for r in range(num_machines):
            path = os.path.join(tmp, f"worker{r}.metrics.json")
            try:
                with open(path, encoding="utf-8") as fh:
                    snap = json.load(fh)
            except (OSError, ValueError):
                continue  # not written yet / torn: skip this scrape
            if not isinstance(snap, dict):
                continue
            for name, v in (snap.get("counters") or {}).items():
                try:
                    out["counters"][_obs.labeled(name, rank=r)] = int(v)
                except (TypeError, ValueError):
                    pass
            for name, v in (snap.get("gauges") or {}).items():
                try:
                    out["gauges"][_obs.labeled(name, rank=r)] = float(v)
                except (TypeError, ValueError):
                    pass
        for r, age in list(hb_ages.items()):
            labels = {"rank": r}
            if slice_of is not None and r in slice_of:
                # per-slice heartbeat labels (docs/OBSERVABILITY.md):
                # dashboards aggregate cadence per slice, the unit the
                # slow-rank detector medians over and recovery respawns
                labels["slice"] = slice_of[r]
            out["gauges"][_obs.labeled("fleet_heartbeat_age_s",
                                       **labels)] = float(age)
        return out

    return collect


def aggregate_fleet_events(tmp: str, num_machines: int,
                           since: float = 0.0) -> str:
    """Merge per-rank worker event JSONLs with the launcher's own
    lifecycle events (worker_spawn/worker_death/fleet_relaunch/
    launch_timeout, stamped rank=None) into ``<tmp>/fleet_events.jsonl``,
    sorted by timestamp.  ``since`` scopes the launcher's process-wide
    event ring to THIS run — a second train_distributed in the same
    process must not replay the previous fleet's deaths into its flight
    recorder.  Torn last lines from crashed workers are skipped, not
    fatal — the file is written on every exit path."""
    own = os.path.join(tmp, "launcher.events.jsonl")
    try:
        with open(own, "w", encoding="utf-8") as fh:
            for rec in _obs.events():
                if rec.get("ts", 0.0) >= since and str(
                        rec.get("kind", "")).startswith(
                        ("worker_", "fleet_", "launch_")):
                    fh.write(json.dumps(rec, default=str) + "\n")
    except OSError:
        own = None
    paths = [os.path.join(tmp, f"worker{r}.events.jsonl")
             for r in range(num_machines)]
    if own is not None:
        paths.append(own)
    out = os.path.join(tmp, "fleet_events.jsonl")
    _obs.merge_event_files(paths, out)
    return out


def aggregate_fleet_metrics(tmp: str, num_machines: int) -> str:
    """Merge per-rank metrics snapshot files (the periodic atomic writes
    each worker's obs layer keeps under ``<tmp>/worker<rank>.metrics.json``)
    into ``<tmp>/fleet_metrics.json`` — schema ``lgbmtpu-fleet-metrics-v1``,
    one entry per rank plus the aggregate (counters SUM, gauges MAX,
    latency reservoirs merged).  Missing rank files (a worker killed
    before its first write) are skipped, not fatal: this runs on success
    AND on every kill/crash exit path, and a partial fleet artifact still
    answers "which rank was behind / who died with what counters"."""
    paths = [os.path.join(tmp, f"worker{r}.metrics.json")
             for r in range(num_machines)]
    out = os.path.join(tmp, "fleet_metrics.json")
    _obs.merge_snapshot_files(paths, out)
    return out


def aggregate_fleet_trace(tmp: str, num_machines: int) -> Optional[str]:
    """Merge per-rank trace exports (each worker's engine writes its span
    ring to ``<tmp>/worker<rank>.trace.json`` via the LGBMTPU_TRACE_FILE
    env the launcher sets) into ``<tmp>/fleet_trace.json`` — one
    clock-aligned Chrome/Perfetto flight recorder, each rank in its own
    pid lane, trace ids and span links joining one request/rollover story
    across ranks.  Completes the events/metrics/trace merge triad.
    Missing rank files (a worker killed before its end-of-run write) are
    skipped, not fatal; returns None when NO rank left a trace."""
    paths = [p for p in (os.path.join(tmp, f"worker{r}.trace.json")
                         for r in range(num_machines))
             if os.path.exists(p)]
    if not paths:
        return None
    out = os.path.join(tmp, "fleet_trace.json")
    _trace.merge_trace_files(paths, out_path=out)
    return out


def _free_ports(k: int) -> list:
    """reference: dask.py _find_n_open_ports."""
    socks, ports = [], []
    for _ in range(k):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _shard_plan(n: int, num_machines: int,
                group: Optional[np.ndarray]) -> Tuple[List[Tuple[int, int]],
                                                      List, int]:
    """Row-shard plan: ((lo, hi) per rank, per-rank query sizes, padded
    per-rank size).  With `group`, shard boundaries snap to query
    boundaries (greedy contiguous fill, like the reference's dask module
    keeping partitions intact per worker)."""
    if group is not None:
        group = np.asarray(group, np.int64)
        if group.sum() != n:
            raise ValueError(
                f"group sizes sum to {group.sum()} but data has {n} rows")
        if len(group) < num_machines:
            raise ValueError(
                f"not enough queries ({len(group)}) for {num_machines} "
                "machines")
        bounds = np.concatenate([[0], np.cumsum(group)])
        shard_slices, shard_groups, q = [], [], 0
        for rank in range(num_machines):
            target = (n * (rank + 1)) // num_machines
            q0, q_cap = q, len(group) - (num_machines - rank - 1)
            q += 1  # at least one query per rank
            while q < q_cap and bounds[q + 1] <= target:
                q += 1
            if rank == num_machines - 1:
                q = len(group)
            shard_slices.append((int(bounds[q0]), int(bounds[q])))
            shard_groups.append(group[q0:q])
        per = max(hi - lo for lo, hi in shard_slices)
        return shard_slices, shard_groups, per
    per = -(-n // num_machines)
    shard_slices = [(r * per, min((r + 1) * per, n))
                    for r in range(num_machines)]
    return shard_slices, [None] * num_machines, per


def _rank_arrays(rank_slices, rank_groups, per, rank, X, y, weight):
    """One rank's (X, y, w, g) with weight-0 padding to the plan's `per`
    (equal shard sizes are a pre_partition requirement; padding rows carry
    weight 0 and, for ranking, one trailing pad query)."""
    lo, hi = rank_slices[rank]
    Xs, ys = X[lo:hi], np.asarray(y)[lo:hi]
    gs = rank_groups[rank]
    pad_s = per - (hi - lo)
    if weight is None and pad_s == 0:
        # no padding, no user weights: keep the unweighted fast paths
        return Xs, ys, np.asarray(()), gs
    ws = (np.asarray(weight, np.float64)[lo:hi]
          if weight is not None else np.ones(hi - lo, np.float64))
    if pad_s:
        Xs = np.concatenate([Xs, np.zeros((pad_s,) + Xs.shape[1:], Xs.dtype)])
        ys = np.concatenate([ys, np.zeros(pad_s, ys.dtype)])
        ws = np.concatenate([ws, np.zeros(pad_s)])
        if gs is not None:
            gs = np.concatenate([gs, [pad_s]])
    return Xs, ys, ws, gs


def train_distributed(
    params: Dict,
    X: np.ndarray,
    y: np.ndarray,
    num_boost_round: int = 100,
    *,
    num_machines: int = 2,
    weight: Optional[np.ndarray] = None,
    group: Optional[np.ndarray] = None,
    eval_set: Optional[Sequence[Tuple]] = None,  # [(Xe, ye), ...]
    eval_weight: Optional[Sequence] = None,
    eval_group: Optional[Sequence] = None,
    eval_names: Optional[Sequence[str]] = None,
    early_stopping_rounds: Optional[int] = None,
    devices_per_machine: int = 1,
    timeout_s: int = 600,
    env_extra: Optional[Dict[str, str]] = None,
    max_restarts: int = 0,
    restart_backoff_s: float = 1.0,
    heartbeat_timeout_s: Optional[float] = None,
    num_slices: Optional[int] = None,
    data_cache: Optional[str] = None,
):
    """Shard rows over `num_machines` local worker processes, train with
    tree_learner=data under pre_partition, and return (rank 0's Booster,
    per-rank model paths).  With eval_set, each eval set is row-sharded the
    same way; metrics sync across ranks (GlobalSyncUpBySum analogue) and
    early stopping fires identically on every rank.

    Worker liveness is supervised by :func:`_watch_workers`: a dead rank
    fails the launch in seconds with its log tail, a HUNG rank (heartbeat
    stale past ``heartbeat_timeout_s``, or the
    ``LGBMTPU_HEARTBEAT_TIMEOUT_S`` env / ``heartbeat_timeout_s`` param
    spelling) is killed and treated exactly like a death, and every
    failure path kills the full worker process groups (no zombies).

    ``max_restarts`` relaunches the whole fleet after a failure (fresh
    ports, re-written shards) with exponential backoff.  With
    ``snapshot_freq`` > 0 in ``params`` the fleet additionally keeps
    COORDINATED checkpoints (rank-0 snapshot + manifest + per-rank acks,
    utils/checkpoint.py), and a relaunch resumes every rank from the
    newest fleet-VALID round instead of round 0 — bitwise-identical to an
    uninterrupted run (docs/ROBUSTNESS.md "Elastic fleet recovery");
    without a valid manifest the relaunch falls back to a from-scratch
    restart, the round-8 behavior.

    ``num_slices`` > 1 (param or config) groups the ranks into slice
    worlds of num_machines/num_slices members each — the loopback
    control-plane form of multi-slice scale-out (docs/ROBUSTNESS.md
    "Slice-granular recovery").  Each slice is
    its own rendezvous world training the shared shard plan; the fleet
    manifests carry slice membership, the slow-rank detector compares
    heartbeats WITHIN a slice, and a rank failure kills + respawns ONLY
    its slice: the replacement resumes from the newest SLICE-valid
    manifest round (every surviving rank's ack present — the lost
    slice's own acks are not required) while the surviving slices never
    stop or restart."""
    import lightgbm_tpu as lgb

    cfg_launch = Config.from_dict(params)
    if num_slices is None:
        num_slices = max(int(cfg_launch.num_slices), 1)
    num_slices = max(int(num_slices), 1)
    ranks_per_slice = num_machines
    slice_of: Optional[Dict[int, int]] = None
    if num_slices > 1:
        if num_machines % num_slices:
            raise ValueError(
                f"num_machines={num_machines} does not divide into "
                f"num_slices={num_slices}")
        ranks_per_slice = num_machines // num_slices
        slice_of = {r: r // ranks_per_slice for r in range(num_machines)}

    if data_cache is not None:
        # rank-sharded cache feed (docs/DISTRIBUTED.md): rows come from
        # one shared save_binary cache; each worker streams ONLY its
        # shard via BinCacheStream(shard=) — the launcher never touches
        # the matrix, and ingest scales with the fleet
        from ..io.stream import BinCacheStream

        if X is not None or y is not None:
            raise ValueError("pass data_cache= XOR (X, y), not both")
        if weight is not None or group is not None or eval_set:
            raise ValueError(
                "data_cache= carries label/weight inside the cache; "
                "explicit weight/group/eval_set are not supported with "
                "the cache feed")
        n = BinCacheStream(data_cache).n_rows  # header read only
    else:
        n = X.shape[0]
    if group is not None:
        group = np.asarray(group, np.int64)
        if weight is None:
            weight = np.ones(n, np.float64)
    # in slice mode the shard plan covers ONE slice's ranks; every slice
    # trains the same plan (global rank r holds shard r % ranks_per_slice)
    shard_slices, shard_groups, per = _shard_plan(n, ranks_per_slice, group)

    for arg_name, arg in (("eval_names", eval_names),
                          ("eval_weight", eval_weight),
                          ("eval_group", eval_group)):
        if arg is not None and len(arg) != len(eval_set or ()):
            raise ValueError(
                f"{arg_name} has {len(arg)} entries but eval_set has "
                f"{len(eval_set or ())}")
    eval_plans = []
    for i, ev in enumerate(eval_set or ()):
        Xe, ye = ev[0], ev[1]
        ge = (np.asarray(eval_group[i], np.int64)
              if eval_group is not None and eval_group[i] is not None
              else None)
        we = (np.asarray(eval_weight[i], np.float64).ravel()
              if eval_weight is not None and eval_weight[i] is not None
              else None)
        ne = np.shape(Xe)[0]  # metadata only — no conversion (jaxlint R14)
        sl, gr, pe = _shard_plan(ne, ranks_per_slice, ge)
        name = (eval_names[i] if eval_names is not None
                else f"valid_{i}")
        eval_plans.append((np.asarray(Xe), np.asarray(ye).ravel(), we,
                           sl, gr, pe, name))

    global _LAST_LAUNCH_DIR
    tmp = _LAST_LAUNCH_DIR = tempfile.mkdtemp(prefix="lgbm_tpu_launch_")
    # fleet checkpoint cadence rides the standard snapshot params; the
    # launcher OWNS snapshotting for its workers (the per-round callback
    # in the worker body runs the manifest protocol), so the params the
    # workers' engine.train sees have snapshot_freq stripped — every rank
    # writing its own local snapshot family would race on shared paths
    # and vouch for nothing fleet-wide
    fleet_freq = max(int(cfg_launch.snapshot_freq), 0)
    fleet_keep = max(int(cfg_launch.snapshot_keep), 0)
    params = {k: v for k, v in dict(params).items()
              if _ALIASES.get(k, k) != "snapshot_freq"}
    if heartbeat_timeout_s is None:
        env_hb = os.environ.get("LGBMTPU_HEARTBEAT_TIMEOUT_S")
        heartbeat_timeout_s = (float(env_hb) if env_hb
                               else float(cfg_launch.heartbeat_timeout_s))
    env_slow = os.environ.get("LGBMTPU_SLOW_RANK_FACTOR")
    slow_rank_factor = (float(env_slow) if env_slow
                        else float(cfg_launch.slow_rank_factor))
    # live fleet observability (docs/OBSERVABILITY.md "Fleet metrics"):
    # the launcher's own /metrics endpoint serves the merged per-rank
    # snapshots + heartbeat ages WHILE workers run.  Opt-in via the same
    # metrics_port=/LGBMTPU_METRICS_PORT gate the trainers use; the
    # collector stays registered after the run (the snapshot files
    # persist), so a post-mortem scrape still sees the last fleet state.
    hb_ages: Dict[int, float] = {}
    _obs.register_collector(
        "fleet_live",
        _fleet_live_collector(tmp, num_machines, hb_ages, slice_of))
    from ..obs import server as _obs_server

    _obs_server.maybe_start(
        int(cfg_launch.metrics_port) if cfg_launch.is_set("metrics_port")
        else None)
    params_path = os.path.join(tmp, "params.npz")
    np.savez(params_path, params=np.asarray(dict(params), dtype=object))
    model_out = os.path.join(tmp, "model.txt")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # per-rank data-shard fingerprints: stamped into the fleet manifest by
    # rank 0 and checked by every resumed rank, so a resume can never
    # continue round k+1 on different data than rounds 1..k trained on.
    # Filled by the first _spawn_all (identical across relaunches — the
    # shard plan is deterministic) and published as one JSON file.
    shard_fps: Dict[str, str] = {}
    shards_json = os.path.join(tmp, "fleet_shards.json")
    # the newest fleet-valid manifest to resume from (set by the restart
    # path after a failure; None = fresh start)
    relaunch = {"resume_manifest": None}

    def _launch_once() -> None:
        # fresh ports per attempt: the previous fleet's listen sockets may
        # sit in TIME_WAIT, and the machines list is baked into the shards
        ports = _free_ports(num_machines)
        workers = []  # (rank, Popen, log_path)
        try:
            _write_shards(ports)
            for rank in range(num_machines):
                _spawn_rank(workers, rank, ports)
        except BaseException:
            # a failure while SPAWNING (disk full, fork failure on a later
            # rank) must not leak the ranks already started — the watchdog
            # cleanup only covers workers it was handed
            for _, p, _ in workers:
                if p.poll() is None:
                    _kill_worker_group(p)
            raise
        slice_restarts = 0
        done: set = set()  # threaded across re-watches (no re-emitted exits)
        while True:
            try:
                _watch_workers(
                    workers, timeout_s,
                    heartbeat_timeout_s=heartbeat_timeout_s or None,
                    heartbeat_paths={
                        r: os.path.join(tmp, f"worker{r}.metrics.json")
                        for r in range(num_machines)},
                    slow_rank_factor=slow_rank_factor,
                    hb_ages=hb_ages, slice_of=slice_of,
                    slice_granular=num_slices > 1, done=done)
                return
            except WorkerFailure as e:
                if e.slice_id is None or slice_restarts >= max_restarts:
                    # not slice-scoped, or the budget is spent: kill any
                    # survivors and hand the failure to the fleet-level
                    # restart path
                    for _, p, _ in workers:
                        if p.poll() is None:
                            _kill_worker_group(p)
                    raise
                slice_restarts += 1
                _respawn_slice(workers, e.slice_id, ports, slice_restarts,
                               done)

    def _respawn_slice(workers, sid: int, ports, attempt: int,
                       done: set) -> None:
        # slice-granular recovery (docs/ROBUSTNESS.md): ONLY the failed
        # slice restarts — from the newest SLICE-valid manifest round
        # (every surviving rank's ack present; the lost slice's own acks
        # cannot be required, its members are dead) — while the
        # surviving slices keep training untouched.  A slice member that
        # already EXITED 0 is not lost: its model file and acks are
        # complete, and respawning it would run an unwatched duplicate.
        lost = tuple(r for r in range(num_machines)
                     if slice_of[r] == sid and r not in done)
        resume_manifest = None
        resumed_round = None
        if fleet_freq > 0:
            fm = _checkpoint.latest_slice_valid_fleet_manifest(
                tmp, num_machines, lost)
            if fm is not None:
                resumed_round, resume_manifest, _ = fm
        _obs.counter("fleet_slice_resumes_total").inc()
        _obs.event("fleet_slice_resume", slice=sid, ranks=list(lost),
                   round=resumed_round, attempt=attempt)
        log_warning(
            f"slice {sid} (ranks {list(lost)}) failed; respawning it "
            + (f"from slice-valid manifest round {resumed_round}"
               if resumed_round is not None else "from scratch")
            + f" — surviving slices keep running (attempt {attempt})")
        excl = ",".join(str(r) for r in lost)
        for rank in lost:
            _spawn_rank(workers, rank, ports,
                        resume_manifest=resume_manifest,
                        exclude_ranks=excl)

    def _write_shards(ports) -> None:
        # phase 1 — write EVERY rank's shard file and publish the full
        # fingerprint table BEFORE any worker starts: rank 0 (spawned
        # first) reads fleet_shards.json once at startup, so writing it
        # while spawning the last rank would race — a manifest with no
        # fingerprints silently disables the changed-data resume guard.
        # In slice mode each slice is its own rendezvous world: global
        # rank r holds local shard r % ranks_per_slice and talks only to
        # its slice's machine list.
        for rank in range(num_machines):
            local = rank % ranks_per_slice
            sid = rank // ranks_per_slice
            slice_ports = ports[sid * ranks_per_slice:
                                (sid + 1) * ranks_per_slice]
            machines = ",".join(f"127.0.0.1:{p}" for p in slice_ports)
            shard_arrays = dict(
                num_machines=ranks_per_slice, machines=machines,
                local_listen_port=ports[rank], time_out=2,
                n_eval=len(eval_plans),
            )
            if data_cache is not None:
                # the cache feed ships NO arrays: the worker streams its
                # shard straight out of the shared cache, and the
                # fingerprint derives from the cache's CRC trailer table
                if str(rank) not in shard_fps:
                    from ..io.stream import cache_shard_fingerprint

                    lo, hi = shard_slices[local]
                    shard_fps[str(rank)] = cache_shard_fingerprint(
                        data_cache, lo, hi)
                np.savez(os.path.join(tmp, f"shard{rank}.npz"),
                         **shard_arrays)
                continue
            Xs, ys, ws, gs = _rank_arrays(shard_slices, shard_groups, per,
                                          local, X, y, weight)
            shard_arrays.update(
                X=Xs, y=ys, w=ws,
                g=(gs if gs is not None else np.asarray(())),
            )
            for i, (Xe, ye, we, sl, gr, pe, name) in enumerate(eval_plans):
                Xv, yv, wv, gv = _rank_arrays(sl, gr, pe, local, Xe, ye, we)
                shard_arrays[f"ev{i}_X"] = Xv
                shard_arrays[f"ev{i}_y"] = yv
                shard_arrays[f"ev{i}_w"] = wv
                shard_arrays[f"ev{i}_g"] = (gv if gv is not None
                                            else np.asarray(()))
                shard_arrays[f"ev{i}_name"] = name
            np.savez(os.path.join(tmp, f"shard{rank}.npz"), **shard_arrays)
            if str(rank) not in shard_fps:
                # fingerprint the shard DATA (not the npz bytes — zip
                # timestamps differ across relaunches): what round k+1
                # must see again for a resume to be sound
                h = hashlib.sha256()
                for arr in (Xs, ys, ws):
                    h.update(np.ascontiguousarray(arr).tobytes())
                if gs is not None:
                    h.update(np.ascontiguousarray(gs).tobytes())
                shard_fps[str(rank)] = h.hexdigest()
        if not os.path.exists(shards_json):
            with open(shards_json, "w", encoding="utf-8") as fh:
                json.dump(shard_fps, fh)

    def _spawn_rank(workers, rank: int, ports,
                    resume_manifest: Optional[str] = None,
                    exclude_ranks: str = "") -> None:
        shard_path = os.path.join(tmp, f"shard{rank}.npz")
        env = dict(os.environ)
        env.update(env_extra or {})
        # the rendezvous rank is slice-local; the worker id is global
        env["LIGHTGBM_TPU_RANK"] = str(rank % ranks_per_slice)
        env["LGBM_TPU_WORKER_ID"] = str(rank)
        env["LGBM_TPU_REPO"] = repo
        env["LGBM_TPU_SHARD"] = shard_path
        env["LGBM_TPU_PARAMS"] = params_path
        env["LGBM_TPU_ROUNDS"] = str(num_boost_round)
        env["LGBM_TPU_MODEL_OUT"] = model_out
        env["LGBM_TPU_ES_ROUNDS"] = str(early_stopping_rounds or 0)
        if data_cache is not None:
            lo, hi = shard_slices[rank % ranks_per_slice]
            env["LGBM_TPU_CACHE"] = os.fspath(data_cache)
            env["LGBM_TPU_CACHE_SHARD"] = f"{lo},{hi},{per}"
        env.pop("PYTEST_CURRENT_TEST", None)
        # per-rank structured event sink (docs/OBSERVABILITY.md): each
        # worker's obs layer appends rank-stamped JSONL records here;
        # the launcher merges them into one fleet-level file afterwards
        env["LGBMTPU_EVENTS_FILE"] = os.path.join(
            tmp, f"worker{rank}.events.jsonl")
        # per-rank metrics flight recorder: the worker body writes
        # atomic snapshots here periodically (and one exact final
        # write on clean exit); aggregate_fleet_metrics merges them
        # into fleet_metrics.json on every exit path — and the hang
        # watchdog reads each rank's heartbeat_ts gauge out of the
        # same file (no extra channel)
        env["LGBMTPU_METRICS_SNAPSHOT_FILE"] = os.path.join(
            tmp, f"worker{rank}.metrics.json")
        # per-rank trace export: the worker's engine writes its span ring
        # here at end of run (a params-level trace_file= still wins
        # inside the worker); aggregate_fleet_trace merges the rank
        # files into fleet_trace.json — the flight recorder's third
        # member.  Per-rank path always: inheriting one shared path from
        # the outer environment would have every rank clobber it.
        env["LGBMTPU_TRACE_FILE"] = os.path.join(
            tmp, f"worker{rank}.trace.json")
        # coordinated fleet checkpoints + resume-to-round relaunch
        # (docs/ROBUSTNESS.md "Elastic fleet recovery")
        if fleet_freq > 0:
            env["LGBMTPU_FLEET_CKPT_DIR"] = tmp
            env["LGBMTPU_FLEET_SNAPSHOT_FREQ"] = str(fleet_freq)
            env["LGBMTPU_FLEET_SNAPSHOT_KEEP"] = str(fleet_keep)
            env["LGBMTPU_FLEET_SHARDS_JSON"] = shards_json
        if num_slices > 1:
            env["LGBMTPU_FLEET_WORLD"] = str(num_machines)
            env["LGBMTPU_FLEET_SLICES"] = json.dumps(
                {str(r): s for r, s in slice_of.items()})
        env["LGBMTPU_SHARD_FINGERPRINT"] = shard_fps[str(rank)]
        if resume_manifest is None and relaunch["resume_manifest"]:
            resume_manifest = relaunch["resume_manifest"]
        if resume_manifest:
            env["LGBMTPU_RESUME_MANIFEST"] = resume_manifest
        if exclude_ranks:
            # slice respawn: the manifest is SLICE-valid (the lost
            # slice's acks are missing by definition); engine.train
            # validates with the lost ranks excluded
            env["LGBMTPU_RESUME_EXCLUDE_RANKS"] = exclude_ranks
        if env.get("LGBMTPU_FAULT"):
            # make injected faults once-only ACROSS restarts, so a
            # relaunched fleet runs clean (utils/faults.py)
            env.setdefault("LGBMTPU_FAULT_ONCE_DIR", tmp)
        # a RELAUNCH must not inherit the previous attempt's metrics
        # snapshot: the old file's static heartbeat_ts would read as a
        # live-but-stalled heartbeat while the new worker is still
        # importing, and the hang watchdog would kill it before its
        # first write
        try:
            os.unlink(env["LGBMTPU_METRICS_SNAPSHOT_FILE"])
        except OSError:
            pass
        # same for a previous attempt's trace export: a relaunched rank
        # must not leave a stale (pre-crash) span file to be merged as
        # if it were this attempt's history
        try:
            os.unlink(env["LGBMTPU_TRACE_FILE"])
        except OSError:
            pass
        # log file instead of a PIPE: a chatty worker cannot deadlock
        # on a full pipe buffer, and the watchdog can harvest tails
        # after the process is gone
        log_path = os.path.join(tmp, f"worker{rank}.log")
        with open(log_path, "wb") as log_fh:
            proc = subprocess.Popen(
                [sys.executable, "-c", _WORKER_SRC], env=env,
                stdout=log_fh, stderr=subprocess.STDOUT,
                start_new_session=True,  # own process group: killable
                # as a unit, no zombies past a timeout
            )
        # a respawned rank replaces its dead entry (the watch loop keys
        # liveness off this list)
        for i, (r, _p, _lp) in enumerate(workers):
            if r == rank:
                workers[i] = (rank, proc, log_path)
                break
        else:
            workers.append((rank, proc, log_path))
        _obs.counter("launcher_worker_spawns_total").inc()
        _obs.event("worker_spawn", worker_rank=rank, pid=proc.pid)

    attempt = 0
    run_started = time.time()  # scopes the event ring to this run's fleet
    try:
        while True:
            try:
                _launch_once()
                break
            except WorkerFailure as e:
                if attempt >= max_restarts:
                    raise
                delay = restart_backoff_s * (2 ** attempt)
                attempt += 1
                # resume-to-round (docs/ROBUSTNESS.md "Elastic fleet
                # recovery"): relaunch from the newest fleet-VALID
                # checkpoint round instead of round 0.  Only a manifest
                # that parses, whose snapshot verifies against its
                # ensemble sha, and that EVERY rank acked qualifies — a
                # crash mid-protocol (the manifest_write window) leaves
                # the previous round authoritative, and no manifest at
                # all falls back to the round-8 from-scratch restart.
                resumed_round = None
                if fleet_freq > 0:
                    fm = _checkpoint.latest_valid_fleet_manifest(
                        tmp, num_machines)
                    if fm is not None:
                        resumed_round, mpath, _ = fm
                        relaunch["resume_manifest"] = mpath
                        _obs.counter("fleet_resumes_total").inc()
                        _obs.gauge("fleet_resumed_round").set(resumed_round)
                        _obs.event("fleet_resume", round=resumed_round,
                                   manifest=mpath, attempt=attempt)
                _obs.counter("launcher_relaunches_total").inc()
                _obs.event("fleet_relaunch", attempt=attempt,
                           backoff_s=delay, cause=str(e)[:200],
                           hung=bool(getattr(e, "hung", False)),
                           resumed_round=resumed_round)
                log_warning(
                    f"launcher attempt {attempt}/{max_restarts + 1} failed "
                    f"({str(e)[:200]}); relaunching all workers in "
                    f"{delay:.1f}s"
                    + (f" from fleet checkpoint round {resumed_round}"
                       if resumed_round is not None else " from scratch"))
                time.sleep(delay)
    finally:
        # fleet-level observability artifact: merge every rank's JSONL
        # event stream (plus the launcher's own lifecycle events) into one
        # time-sorted file — written on success AND on failure, so a dead
        # fleet still leaves its flight recorder behind.  Best-effort: a
        # full disk here must not cost a trained model (nor mask the real
        # WorkerFailure on the failure path)
        try:
            fleet_events = aggregate_fleet_events(tmp, num_machines,
                                                  since=run_started)
        except OSError as e:
            log_warning(f"could not write fleet_events.jsonl: {e}")
            fleet_events = None
        # the metrics twin: merge whatever per-rank snapshot files exist
        # (periodic atomic writes survive kills) — success AND kill paths
        try:
            fleet_metrics = aggregate_fleet_metrics(tmp, num_machines)
        except OSError as e:
            log_warning(f"could not write fleet_metrics.json: {e}")
            fleet_metrics = None
        # the trace twin, completing the triad: merge whatever per-rank
        # trace exports exist into one clock-aligned flight recorder
        try:
            fleet_trace = aggregate_fleet_trace(tmp, num_machines)
        except (OSError, ValueError) as e:
            log_warning(f"could not write fleet_trace.json: {e}")
            fleet_trace = None
    booster = lgb.Booster(model_file=model_out + ".rank0")
    booster._fleet_events = fleet_events
    booster._fleet_metrics = fleet_metrics
    booster._fleet_trace = fleet_trace
    meta_path = model_out + ".meta.json"
    if os.path.exists(meta_path):
        with open(meta_path) as fh:
            meta = json.load(fh)
        booster.best_iteration = int(meta.get("best_iteration", -1))
        booster.best_score = meta.get("best_score", {})
        booster._distributed_evals_result = meta.get("evals_result", {})
    return booster, [
        model_out + f".rank{r}" for r in range(num_machines)
    ]
