"""Training entry points: train() and cv().

Reference: python-package/lightgbm/engine.py — train(), cv(), CVBooster,
callback ordering by `.order` / `.before_iteration`, EarlyStopException flow.
"""

from __future__ import annotations

import copy
import os
import time
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from .basic import Booster, CorruptModelError, Dataset, LightGBMError
from .callback import CallbackEnv, EarlyStopException
from .config import Config, choose_param_value
from .obs import metrics as _obs
from .obs import server as _obs_server
from .obs import trace as _trace
from .utils import checkpoint as _checkpoint
from .utils import faults as _faults
from .utils.log import log_debug, log_info, log_warning, set_verbosity


def _load_init_booster(init_model) -> Booster:
    """Booster from init_model; a snapshot that fails integrity
    verification falls back to the newest VALID snapshot in its family
    instead of dying on (or worse, silently half-loading) a torn file
    (docs/ROBUSTNESS.md)."""
    if isinstance(init_model, Booster):
        return init_model
    try:
        return Booster(model_file=init_model)
    except CorruptModelError as corrupt:
        # scan strictly OLDER siblings: a stale NEWER snapshot (from a
        # previous, longer run sharing the prefix) would resume with the
        # wrong trees — older-than-requested is the only safe direction
        below = _checkpoint.snapshot_iteration(init_model)
        fb = _checkpoint.latest_valid_snapshot(init_model, below_iter=below)
        if fb is not None:
            it, snap = fb
            _obs.counter("checkpoint_fallbacks_total").inc()
            _obs.event("checkpoint_fallback", requested=str(init_model),
                       used=snap, iteration=it)
            log_warning(
                f"init_model {init_model} failed integrity verification; "
                f"falling back to the newest valid older snapshot {snap} "
                f"(iteration {it})")
            return Booster(model_file=snap)
        # last resort: a PRE-TRAILER-ERA snapshot (no trailer at all but
        # otherwise intact) — load unverified rather than abandoning the
        # whole checkpoint family.  A truncated file usually loses its
        # trailer too and looks identical, and the parser tolerates
        # missing tail blocks — so demand the format's own structural
        # completeness markers ("end of trees" + every tree block the
        # tree_sizes header promises) before the benefit of the doubt.
        text, ok = _checkpoint.read_and_verify(init_model)
        if ok is None and "\nend of trees" in text:
            import re as _re

            m = _re.search(r"^tree_sizes=(.*)$", text, _re.M)
            expected_trees = len(m.group(1).split()) if m else -1
            try:
                booster = Booster(model_str=text)
            except Exception:  # noqa: BLE001 — torn after all
                raise corrupt from None
            if booster.num_trees() != expected_trees:
                raise corrupt from None
            log_warning(
                f"init_model {init_model} is a snapshot with no integrity "
                "trailer (pre-trailer format); no verified fallback exists "
                "— loading it UNVERIFIED as a last resort. Re-snapshot "
                "after this run to upgrade the family "
                "(docs/ROBUSTNESS.md)")
            return booster
        raise


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[List[Dataset]] = None,
    valid_names: Optional[List[str]] = None,
    feval: Optional[Callable] = None,
    init_model: Optional[Union[str, Booster]] = None,
    keep_training_booster: bool = False,
    callbacks: Optional[List[Callable]] = None,
    resume: Optional[str] = None,
) -> Booster:
    """reference: engine.py train().

    ``resume="auto"`` (ours; also reachable as the ``resume=auto`` config/CLI
    param): pick up the newest VALID snapshot in ``output_model``'s family
    (utils/checkpoint.py latest_valid_snapshot) without naming a file, and
    train only the REMAINING rounds toward ``num_boost_round`` — crash
    recovery becomes re-running the original command (docs/ROBUSTNESS.md;
    the round-8 fallback handled a torn *named* snapshot, this closes the
    queued round-9 follow-up of not having to name one at all)."""
    params = dict(params or {})
    params = choose_param_value("num_iterations", params, None)
    if params.get("num_iterations") is not None:
        num_boost_round = int(params["num_iterations"])
    params["num_iterations"] = num_boost_round
    params = choose_param_value("early_stopping_round", params, None)
    early_stopping_round = params.get("early_stopping_round")
    cfg_probe = Config.from_dict(params)
    set_verbosity(cfg_probe.verbosity)
    # live introspection opt-in (docs/OBSERVABILITY.md): metrics_port= (or
    # LGBMTPU_METRICS_PORT) starts the process-wide /metrics + /healthz
    # endpoint before the first round, so the whole run is scrapeable.
    # Port conflicts fall back to an ephemeral port; nothing here may
    # cost the caller a model.
    telemetry_on = (bool(cfg_probe.telemetry) if cfg_probe.is_set("telemetry")
                    else _obs.DEFAULT_ENABLED)
    if telemetry_on:
        try:
            _obs_server.maybe_start(
                cfg_probe.metrics_port if cfg_probe.is_set("metrics_port")
                else None)
        except OSError as e:
            # an unbindable endpoint (fd exhaustion, no loopback in a
            # sandbox) must never cost the caller a model — the fallback
            # inside start() covers busy ports; this covers everything else
            log_warning(f"metrics endpoint could not start: {e}")

    resume = resume if resume is not None else (cfg_probe.resume or None)
    if resume is not None and resume != "auto":
        # resume=<fleet manifest> (docs/ROBUSTNESS.md "Elastic fleet
        # recovery"): the launcher's relaunch path hands every rank the
        # newest FLEET-VALID manifest; a torn or unconfirmed one is
        # refused outright — resuming into inconsistent fleet state would
        # silently fork the ranks' models.
        if init_model is not None:
            # precedence decided FIRST: a manifest that will be ignored
            # must not be able to abort the run on its own staleness
            log_warning("resume=<manifest> ignored: an explicit init_model "
                        "was given and takes precedence")
        else:
            if not os.path.exists(resume):
                raise LightGBMError(
                    f"resume={resume!r} is not supported: pass 'auto', a "
                    "fleet manifest path (lgbmtpu-fleet-ckpt-v1), or "
                    "init_model=<snapshot> for a specific file")
            # slice-granular recovery (docs/ROBUSTNESS.md): the launcher
            # respawning ONE lost slice names its dead ranks here, so a
            # round every SURVIVING rank confirmed is resumable even
            # though the lost slice's own acks are missing
            excl = tuple(
                int(r) for r in os.environ.get(
                    "LGBMTPU_RESUME_EXCLUDE_RANKS", "").split(",") if r)
            manifest = _checkpoint.fleet_manifest_valid(
                resume, exclude_ranks=excl)
            if manifest is None:
                raise LightGBMError(
                    f"resume manifest {resume} is not fleet-valid (torn, "
                    "unconfirmed by some rank, or its snapshot fails "
                    "verification) — refusing to resume into inconsistent "
                    "fleet state (docs/ROBUSTNESS.md)")
            rank = os.environ.get("LGBM_TPU_WORKER_ID",
                                  os.environ.get("LIGHTGBM_TPU_RANK", "0"))
            shard_fp = os.environ.get("LGBMTPU_SHARD_FINGERPRINT")
            want_fp = (manifest.get("shards") or {}).get(rank)
            if shard_fp and want_fp and shard_fp != want_fp:
                raise LightGBMError(
                    f"rank {rank}'s data shard fingerprint {shard_fp[:12]}… "
                    f"does not match the manifest's {want_fp[:12]}… — the "
                    "shard changed since the checkpoint; resuming would "
                    "train round k+1 on different data than rounds 1..k")
            it = int(manifest["round"])
            if it > num_boost_round:
                # overshoot guard (the resume='auto' branch bounds its
                # scan with below_iter for the same reason): silently
                # returning a model with MORE iterations than requested
                # is the stale-newer hazard, not a resume
                raise LightGBMError(
                    f"resume manifest {resume} is at round {it}, beyond "
                    f"the requested num_iterations={num_boost_round} — "
                    "raise num_iterations or resume from an older "
                    "manifest")
            init_model = manifest["snapshot"]
            num_boost_round = max(num_boost_round - it, 0)
            _obs.counter("fleet_resumes_total").inc()
            _obs.gauge("fleet_resumed_round").set(it)
            _obs.event("fleet_resume", round=it, manifest=os.fspath(resume),
                       snapshot=manifest["snapshot"])
            # the resume leg joins the trace vocabulary (ISSUE-20): a
            # rollover/relaunch reconstructs from the merged fleet trace
            # next to the serve/request spans it interleaved with
            _trace.record_span("checkpoint.resume", 0.0, round=it,
                               manifest=os.fspath(resume),
                               outcome="fleet_manifest")
            log_info(
                f"resume: fleet manifest {resume} (round {it}) — training "
                f"{num_boost_round} remaining round(s) from its snapshot")
    elif resume is not None:
        if init_model is not None:
            log_warning("resume='auto' ignored: an explicit init_model was "
                        "given and takes precedence")
        else:
            # restrict to snapshots AT OR BELOW the target iteration: a
            # newer snapshot from a previous, longer run sharing the prefix
            # would overshoot the requested model (the same stale-newer
            # hazard the torn-snapshot fallback guards against)
            fb = _checkpoint.latest_valid_snapshot(
                cfg_probe.output_model, below_iter=num_boost_round + 1)
            if fb is not None:
                it, snap = fb
                init_model = snap
                num_boost_round = max(num_boost_round - it, 0)
                _trace.record_span("checkpoint.resume", 0.0, round=it,
                                   snapshot=os.fspath(snap),
                                   outcome="auto_snapshot")
                log_info(
                    f"resume=auto: resuming from {snap} (iteration {it}); "
                    f"training {num_boost_round} remaining round(s)")
            else:
                log_info("resume=auto: no valid snapshot found for "
                         f"{cfg_probe.output_model}; starting fresh")

    fobj = None
    if callable(params.get("objective")):
        fobj = params["objective"]
        params["objective"] = "none"

    booster = Booster(params=params, train_set=train_set)
    if init_model is not None:
        init_booster = _load_init_booster(init_model)
        # continued training (reference: GBDT continued training via
        # input_model): seed with the source model's trees, then replay
        # scores so the fresh booster's own boost_from_average must not
        # contribute twice.
        import numpy as _np
        from .models.gbdt import GBDT as _GBDT

        gbdt = booster._gbdt
        src = init_booster._gbdt
        if src.average_output:
            # RF keeps the folded round-trip: averaged output folds the
            # init score into EVERY tree, so the separated-init replay
            # below would double-count it
            seeded = _GBDT.load_model_from_string(
                init_booster.model_to_string())
            gbdt.models = seeded.models
            gbdt.iter_ = seeded.iter_
            gbdt.init_scores = [0.0] * gbdt.num_tree_per_iteration
        else:
            # seed with the source's EXACT state: pure-delta trees plus
            # the init score kept separate (raw-delta snapshots and
            # in-memory boosters carry it; legacy folded model files load
            # with init_scores == 0 and folded trees, which reduces to the
            # old behavior).  Rebuilding the score base as fl32(init) and
            # replaying fl32(delta) per tree reproduces the live run's
            # accumulation order, so crash-resume from a raw-delta
            # snapshot is BITWISE-identical to uninterrupted training
            # (docs/ROBUSTNESS.md "Elastic fleet recovery").
            gbdt.models = copy.deepcopy(src.models)
            gbdt.iter_ = (len(src.models)
                          // max(gbdt.num_tree_per_iteration, 1))
            gbdt.init_scores = list(src.init_scores)
        base = _np.zeros(gbdt._score.shape, dtype=_np.float32)
        if any(s != 0.0 for s in gbdt.init_scores):
            if gbdt.num_tree_per_iteration == 1:
                base += _np.float32(gbdt.init_scores[0])
            else:
                base += _np.asarray(gbdt.init_scores,
                                    dtype=_np.float32)[None, :]
        if train_set.init_score is not None:
            base += _np.asarray(train_set.init_score, _np.float32).reshape(base.shape)
        import jax.numpy as _jnp

        gbdt._score = _jnp.asarray(base)
        _replay_scores(gbdt)

    valid_sets = valid_sets or []
    valid_names = valid_names or []
    for i, vs in enumerate(valid_sets):
        if vs is train_set:
            name = valid_names[i] if i < len(valid_names) else "training"
            booster._gbdt.train_name = name
            continue
        name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
        booster.add_valid(vs, name)

    callbacks = list(callbacks or [])
    if early_stopping_round is not None and int(early_stopping_round) > 0:
        from .callback import early_stopping

        callbacks.append(
            early_stopping(
                int(early_stopping_round),
                first_metric_only=bool(params.get("first_metric_only", False)),
                verbose=cfg_probe.verbosity >= 1,
                min_delta=float(params.get("early_stopping_min_delta", 0.0)),
            )
        )
    for cb in callbacks:
        if not hasattr(cb, "order"):
            cb.order = 0  # type: ignore[attr-defined]
    callbacks_before = [cb for cb in callbacks if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: cb.order)
    callbacks_after.sort(key=lambda cb: cb.order)

    train_in_valids = any(vs is train_set for vs in (valid_sets or []))

    snapshot_freq = int(cfg_probe.snapshot_freq)
    # snapshot names carry GLOBAL iteration numbers: a resumed run (this
    # call's round i continues init_model's iterations) must not overwrite
    # snapshot_iter_2 with a 6-tree model — the fallback scan and the
    # "train (total - k) more rounds" resume recipe both trust the name
    snapshot_base = booster.current_iteration()

    # request-scoped tracing knobs apply process-wide here, like the
    # registry's enablement — admission points (serve submit, /predict)
    # read them when minting per-request contexts
    _trace.configure_request_tracing(cfg_probe.request_tracing,
                                     cfg_probe.trace_sample)
    trace_out = _trace_path(cfg_probe)
    if _obs.enabled() and trace_out:
        # ring-overflow spill sink rides the trace_file= opt-in
        # (obs/trace.py): a long (out-of-core) run can no longer lose
        # spans silently — evictions append to the sidecar JSONL and
        # count trace_spans_spilled_total.  Best-effort, like the final
        # write_trace: an unwritable sidecar must not cost the run.
        try:
            _trace.enable_spill(trace_out + ".spill.jsonl")
        except OSError as e:
            log_warning("could not arm the trace spill sink next to "
                        f"{trace_out}: {e}")

    # the run-level span is HOST-CAUSAL wall clock (docs/OBSERVABILITY.md
    # "Span tracing")
    train_span = _trace.span("train", num_boost_round=num_boost_round)
    train_span.__enter__()
    # arm the heartbeat: heartbeat_done=0 marks this process as actively
    # training, so the launcher's hang watchdog tracks staleness; the
    # finally below retires it — otherwise the post-training tail (model
    # save, final eval, fleet ack) would read as a stalled heartbeat and
    # a slow endgame could be killed as a false hang
    _obs.gauge("heartbeat_done").set(0.0)
    try:
        for i in range(num_boost_round):
            # heartbeat (docs/ROBUSTNESS.md "Elastic fleet recovery"): a
            # monotonic host-clock gauge bumped by the MAIN thread each
            # round and flushed by the existing periodic metrics snapshot
            # — the launcher's hang watchdog declares a rank hung when
            # the VALUE stops changing, so a rank wedged inside a
            # collective is caught even though its snapshot-writer daemon
            # thread keeps the file fresh.  One host gauge write: zero
            # device dispatches, zero new threads.
            _obs.gauge("heartbeat_ts").set(time.monotonic())
            # fault-injection sites: preemption (hard exit) or a wedged
            # collective (sleep forever) at the start of 1-based iteration
            # i+1 (utils/faults.py; recovery = manifest/snapshot resume)
            _faults.maybe_crash("host_crash", i + 1)
            _faults.maybe_hang("worker_hang", i + 1)
            for cb in callbacks_before:
                cb(CallbackEnv(booster, params, i, 0, num_boost_round, []))
            finished = booster.update(fobj=fobj)
            evaluation_result_list = []
            if train_in_valids or booster._gbdt.cfg.is_provide_training_metric:
                evaluation_result_list.extend(booster.eval_train(feval))
            evaluation_result_list.extend(booster.eval_valid(feval))
            for cb in callbacks_after:
                cb(CallbackEnv(booster, params, i, 0, num_boost_round, evaluation_result_list))
            global_iter = snapshot_base + i + 1
            if snapshot_freq > 0 and global_iter % snapshot_freq == 0:
                # periodic failure-recovery snapshot (reference: CLI
                # snapshot_freq / save_period — GBDT::Train saves
                # model_output_path.snapshot_iter_<n> every freq iterations)
                snap = f"{cfg_probe.output_model}.snapshot_iter_{global_iter}"
                # atomic + integrity-trailed (utils/checkpoint.py): a crash
                # mid-write can no longer leave a torn snapshot that a
                # restart would load.  raw_deltas: snapshots carry pure-delta
                # trees + an init_scores header so resume is bitwise
                with _trace.span("checkpoint.snapshot",
                                 iteration=global_iter, path=snap):
                    _checkpoint.save_snapshot(
                        snap, booster.model_to_string(raw_deltas=True),
                        global_iter)
                log_info(f"Saved snapshot to {snap}")
                if int(cfg_probe.snapshot_keep) > 0:
                    # bounded retention (snapshot_keep=): prune the oldest
                    # snapshots AFTER the new one landed; the newest
                    # verifying snapshot is never pruned
                    _checkpoint.prune_snapshots(cfg_probe.output_model,
                                                int(cfg_probe.snapshot_keep))
            if finished:
                log_info("Stopped training because there are no more leaves that meet the split requirements")
                break
    except EarlyStopException as e:
        booster.best_iteration = e.best_iteration + 1
        for item in e.best_score:
            booster.best_score.setdefault(item[0], {})[item[1]] = item[2]
        train_span.set(early_stopped=True)
    finally:
        # retire the heartbeat BEFORE the endgame (save/eval/ack tail can
        # legitimately exceed the hang timeout); the periodic snapshot
        # flushes it within one period
        _obs.gauge("heartbeat_done").set(1.0)
        train_span.set(trained_iterations=booster.current_iteration())
        train_span.__exit__(None, None, None)
        # report (and the spill-sink disarm inside it) must run on EVERY
        # exit path — a fault/non-finite abort that skipped it would leave
        # the sink armed process-wide, appending later unrelated work's
        # evictions to this run's sidecar
        _finish_run_report(cfg_probe)
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration()
    return booster


def serve(model=None, params: Optional[Dict[str, Any]] = None, *,
          models=None, start: bool = True):
    """Serving entry point (README "Serving"): build — and by default
    START — an in-process :class:`~lightgbm_tpu.serve.ServingRuntime`
    over one or more trained models, with the live ``/metrics`` +
    ``/healthz`` endpoint brought up exactly as ``train`` does.

    ``model`` is a :class:`Booster` or a model-file path (single-model,
    served as ``"default"``); ``models`` is a ``{name: Booster|path}``
    table for multi-tenant serving.  ``params`` carries the serve knobs
    (``serve_max_wait_ms``, ``serve_max_queue``, ``serve_slo_p99_ms``,
    ``serve_tenant_quota``) plus ``metrics_port=``/``telemetry=`` — the
    same Config names as everywhere else (docs/Parameters.md).  Setting
    ANY fleet knob (``serve_replicas``, ``serve_deadline_ms``,
    ``serve_hedge_ms``, ``serve_retry_budget``, ``serve_replica_trip``,
    ``serve_replica_cooldown_ms``, ``serve_hang_timeout_ms``,
    ``serve_restart_backoff_ms``, ``serve_max_restarts``) builds a
    :class:`~lightgbm_tpu.serve.ServingFleet` instead — health-routed
    replicas, deadlines, exactly-once retry and the restart watchdog.

    >>> rt = lgb.serve(booster, {"serve_max_wait_ms": 2})
    >>> y = rt.predict(X); rt.stop()
    >>> fl = lgb.serve(booster, {"serve_replicas": 2,
    ...                          "serve_deadline_ms": 50})
    """
    from .serve.fleet import ServingFleet
    from .serve.runtime import ServingRuntime

    cfg = Config.from_dict(dict(params or {}))
    set_verbosity(cfg.verbosity)
    telemetry_on = (bool(cfg.telemetry) if cfg.is_set("telemetry")
                    else _obs.DEFAULT_ENABLED)
    _obs.set_enabled(telemetry_on)
    if telemetry_on:
        try:
            _obs_server.maybe_start(
                cfg.metrics_port if cfg.is_set("metrics_port") else None)
        except OSError as e:
            log_warning(f"metrics endpoint could not start: {e}")

    def _load(m):
        return m if isinstance(m, Booster) else Booster(model_file=m)

    table = None if models is None else {n: _load(m)
                                         for n, m in models.items()}
    single = None if model is None else _load(model)
    kw = {}
    for name, param in (("max_wait_ms", "serve_max_wait_ms"),
                        ("max_queue", "serve_max_queue"),
                        ("slo_p99_ms", "serve_slo_p99_ms"),
                        ("tenant_quota", "serve_tenant_quota")):
        if cfg.is_set(param):
            kw[name] = getattr(cfg, param)
    fleet_kw = {}
    for name, param in (("replicas", "serve_replicas"),
                        ("deadline_ms", "serve_deadline_ms"),
                        ("hedge_ms", "serve_hedge_ms"),
                        ("retry_budget", "serve_retry_budget"),
                        ("trip", "serve_replica_trip"),
                        ("cooldown_ms", "serve_replica_cooldown_ms"),
                        ("hang_timeout_ms", "serve_hang_timeout_ms"),
                        ("restart_backoff_ms", "serve_restart_backoff_ms"),
                        ("max_restarts", "serve_max_restarts")):
        if cfg.is_set(param):
            fleet_kw[name] = getattr(cfg, param)
    if fleet_kw:
        return ServingFleet(single, models=table, start=start,
                            **kw, **fleet_kw)
    return ServingRuntime(single, models=table, start=start, **kw)


def continual_train(model=None, params: Optional[Dict[str, Any]] = None, *,
                    runtime=None, model_name: str = "default",
                    reference=None, state_dir: Optional[str] = None,
                    cache_path: Optional[str] = None,
                    start: bool = True, **runner_kwargs):
    """Continual-training entry point (README "Continuous training"):
    build — and by default START — a
    :class:`~lightgbm_tpu.continual.ContinualRunner` that ingests fresh
    data beside a live :class:`~lightgbm_tpu.serve.ServingRuntime`,
    periodically refits/appends on-device, and hot-swaps the serving
    ensemble with zero downtime.  The live ``/metrics`` + ``/healthz``
    endpoint comes up exactly as ``train``/``serve`` bring it up.

    ``model`` is a :class:`Booster` or model-file path; ``runtime`` an
    optional ServingRuntime already serving it under ``model_name``;
    ``reference`` the training Dataset (or its ``save_binary`` cache
    path) carrying the FROZEN bin mappers; ``params`` the policy knobs
    (``update_every_rows``, ``update_every_s``, ``append_trees``,
    ``drift_window``) plus the usual ``metrics_port=``/``telemetry=``.
    ``state_dir`` arms durable rollover checkpoints (+ ``resume=True``
    in ``runner_kwargs`` to pick the newest fleet-valid one up);
    ``cache_path`` arms the durable CRC'd ingest cache.

    >>> rt = lgb.serve(booster)
    >>> cr = lgb.continual_train(booster, {"update_every_rows": 4096},
    ...                          runtime=rt, reference=train_ds)
    """
    from .continual.runtime import ContinualRunner

    cfg = Config.from_dict(dict(params or {}))
    set_verbosity(cfg.verbosity)
    telemetry_on = (bool(cfg.telemetry) if cfg.is_set("telemetry")
                    else _obs.DEFAULT_ENABLED)
    _obs.set_enabled(telemetry_on)
    if telemetry_on:
        try:
            _obs_server.maybe_start(
                cfg.metrics_port if cfg.is_set("metrics_port") else None)
        except OSError as e:
            log_warning(f"metrics endpoint could not start: {e}")
    bst = model if isinstance(model, Booster) else Booster(model_file=model)
    for name in ("update_every_rows", "update_every_s", "append_trees",
                 "drift_window"):
        if cfg.is_set(name):
            runner_kwargs.setdefault(name, getattr(cfg, name))
    return ContinualRunner(bst, runtime=runtime, model_name=model_name,
                           reference=reference, state_dir=state_dir,
                           cache_path=cache_path, start=start,
                           **runner_kwargs)


def _trace_path(cfg: Config) -> str:
    """The run's trace-export path: ``trace_file=`` when set, else the
    ``LGBMTPU_TRACE_FILE`` env spelling (the launcher sets a per-rank
    path so ``aggregate_fleet_trace`` can merge the fleet's files)."""
    return cfg.trace_file or os.environ.get("LGBMTPU_TRACE_FILE", "")


def _finish_run_report(cfg: Config) -> None:
    """End-of-run observability (docs/OBSERVABILITY.md): the reference-style
    "Time for X / counter = v" report through the logger (debug verbosity —
    the TIMETAG analogue, quiet by default), and the machine-readable
    snapshot to ``metrics_file=`` when configured (atomic JSON; render with
    ``python -m lightgbm_tpu.obs <file>``)."""
    if not _obs.enabled():
        for name, val in (("metrics_file", cfg.metrics_file),
                          ("trace_file", _trace_path(cfg))):
            if val:
                log_warning(f"{name}={val} ignored: telemetry is disabled "
                            "(telemetry=false / LGBMTPU_TELEMETRY=0)")
        return
    snap = _obs.snapshot()
    for line in _obs.render_lightgbm(snap):
        log_debug(line)
    if cfg.metrics_file:
        # best-effort: an unwritable metrics path must never cost the
        # caller a fully trained booster
        try:
            _obs.write_snapshot(cfg.metrics_file, snap)
        except OSError as e:
            log_warning(f"could not write metrics snapshot to "
                        f"{cfg.metrics_file}: {e}")
        else:
            log_info(f"Metrics snapshot written to {cfg.metrics_file}")
    trace_out = _trace_path(cfg)
    if trace_out:
        # Chrome-trace/Perfetto span export (obs/trace.py); same
        # best-effort contract as metrics_file
        try:
            n_spans = _trace.write_trace(trace_out)
        except OSError as e:
            log_warning(f"could not write trace to {trace_out}: {e}")
        else:
            log_info(f"Trace ({n_spans} spans) written to {trace_out}")
        # disarm the run's spill sink: evictions from LATER work in this
        # process (another train, serving) must not append to — and be
        # mistaken for — this run's span history
        _trace.disable_spill()


def _replay_scores(gbdt) -> None:
    """Recompute train scores from existing trees (continued training).
    The per-tree f32 adds run in training order, so a resume from a
    raw-delta snapshot reproduces the live score state bitwise
    (docs/ROBUSTNESS.md "Elastic fleet recovery")."""
    import numpy as _np

    import jax.numpy as jnp

    if (getattr(gbdt.train_set, "ooc_spill", False) and len(gbdt.models) > 1
            and all(t.num_cat == 0 for t in gbdt.models)):
        # spill regime: one stream sweep for the whole ensemble — a
        # per-tree replay would re-decompress the bin cache T times.
        # Categorical trees fall through to the per-tree loop below
        # (predict_leaf_binned_tree streams them host-chunk-wise): slower
        # (one sweep per tree) but a resume must never fail over it.
        _replay_scores_streamed(gbdt)
        return
    k = gbdt.num_tree_per_iteration
    for i, tree in enumerate(gbdt.models):
        c = i % k
        if tree.is_linear:
            # linear leaves carry per-leaf linear terms — a
            # leaf_value-only replay would silently drop them (mirror of
            # GBDT.add_valid's continued-training replay)
            vals = jnp.asarray(
                tree.predict_batch(_np.asarray(gbdt.train_set.raw_device)),
                jnp.float32)
        else:
            leaf = gbdt.train_set.predict_leaf_binned_tree(tree)
            vals = jnp.asarray(tree.leaf_value, jnp.float32)[leaf]
        if k == 1:
            gbdt._score = gbdt._score + vals
        else:
            gbdt._score = gbdt._score.at[:, c].add(vals)


def _replay_scores_streamed(gbdt) -> None:
    """Spill-regime replay: ONE sequential pass over the bin stream for
    ALL trees (Dataset.predict_leaf_binned_trees_chunked), folding each
    chunk's per-tree f32 leaf values into the score in training order —
    the same per-row add sequence as the tree-at-a-time replay, so the
    result stays bitwise while the cache is decompressed once instead of
    once per tree."""
    import numpy as _np

    import jax.numpy as jnp

    k = gbdt.num_tree_per_iteration
    trees = gbdt.models
    leaf_vals = [jnp.asarray(t.leaf_value, jnp.float32) for t in trees]
    parts = []
    for _row_lo, valid, leaf in gbdt.train_set.predict_leaf_binned_trees_chunked(trees):
        chunk = gbdt._score[_row_lo:_row_lo + valid] if k == 1 else \
            gbdt._score[_row_lo:_row_lo + valid, :]
        for i in range(len(trees)):
            vals = leaf_vals[i][leaf[i, :valid]]
            if k == 1:
                chunk = chunk + vals
            else:
                chunk = chunk.at[:, i % k].add(vals)
        parts.append(chunk)
    if parts:
        gbdt._score = jnp.concatenate(parts, axis=0)


class CVBooster:
    """reference: engine.py CVBooster — container of per-fold boosters."""

    def __init__(self, boosters: Optional[List[Booster]] = None):
        self.boosters = boosters or []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]

        return handler_function


def _make_n_folds(full_data: Dataset, nfold: int, params: Dict, seed: int,
                  stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    rng = np.random.RandomState(seed)
    if full_data.group is not None:
        # ranking: folds must respect query boundaries (reference: cv's
        # _make_n_folds group-aware split)
        nq = len(full_data.group)
        qidx = np.arange(nq)
        if shuffle:
            rng.shuffle(qidx)
        bounds = np.concatenate([[0], np.cumsum(full_data.group)]).astype(np.int64)
        for q_chunk in np.array_split(qidx, nfold):
            te = np.concatenate([np.arange(bounds[q], bounds[q + 1]) for q in q_chunk])
            te = np.sort(te)
            tr = np.setdiff1d(np.arange(num_data), te)
            yield tr, te
        return
    if stratified and full_data.label is not None:
        label = np.asarray(full_data.label)
        classes = np.unique(label)
        folds = [[] for _ in range(nfold)]
        for c in classes:
            idx = np.nonzero(label == c)[0]
            if shuffle:
                rng.shuffle(idx)
            for i, chunk in enumerate(np.array_split(idx, nfold)):
                folds[i].extend(chunk.tolist())
        test_indices = [np.asarray(sorted(f), dtype=np.int64) for f in folds]
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        test_indices = [np.sort(chunk) for chunk in np.array_split(idx, nfold)]
    for te in test_indices:
        tr = np.setdiff1d(np.arange(num_data), te)
        yield tr, te


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics=None,
    feval=None,
    init_model=None,
    fpreproc=None,
    seed: int = 0,
    callbacks=None,
    eval_train_metric: bool = False,
    return_cvbooster: bool = False,
) -> Dict[str, Any]:
    """reference: engine.py cv()."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    params = choose_param_value("num_iterations", params, None)
    if params.get("num_iterations") is not None:
        num_boost_round = int(params["num_iterations"])
    params.pop("num_iterations", None)
    params = choose_param_value("early_stopping_round", params, None)
    early_stopping_round = params.get("early_stopping_round")
    objective = params.get("objective", "")
    stratified = stratified and isinstance(objective, str) and (
        objective.startswith("binary") or objective.startswith("multiclass")
    )

    train_set.construct()
    if folds is None:
        folds = list(_make_n_folds(train_set, nfold, params, seed, stratified, shuffle))
    elif hasattr(folds, "split"):
        folds = list(folds.split(np.zeros(train_set.num_data()), np.asarray(train_set.label)))

    cvbooster = CVBooster()
    fold_valid_sets = []
    for tr_idx, te_idx in folds:
        tr = train_set.subset(tr_idx)
        te = train_set.subset(te_idx)
        bst = Booster(params=params, train_set=tr)
        bst.add_valid(te, "valid")
        cvbooster.append(bst)
        fold_valid_sets.append(te)

    callbacks = list(callbacks or [])
    if early_stopping_round is not None and int(early_stopping_round) > 0:
        from .callback import early_stopping

        callbacks.append(early_stopping(int(early_stopping_round), verbose=False))
    for cb in callbacks:
        if not hasattr(cb, "order"):
            cb.order = 0  # type: ignore[attr-defined]
    cb_before = sorted([c for c in callbacks if getattr(c, "before_iteration", False)], key=lambda c: c.order)
    cb_after = sorted([c for c in callbacks if not getattr(c, "before_iteration", False)], key=lambda c: c.order)

    results: Dict[str, List[float]] = {}
    try:
        for i in range(num_boost_round):
            for cb in cb_before:
                cb(CallbackEnv(cvbooster, params, i, 0, num_boost_round, []))
            merged: Dict[tuple, List[float]] = {}
            for bst in cvbooster.boosters:
                bst.update()
                evals = bst.eval_valid(feval)
                if eval_train_metric:
                    evals = bst.eval_train(feval) + evals
                for (name, metric, val, hib) in evals:
                    merged.setdefault((name, metric, hib), []).append(val)
            agg = []
            for (name, metric, hib), vals in merged.items():
                mean, std = float(np.mean(vals)), float(np.std(vals))
                results.setdefault(f"{name} {metric}-mean", []).append(mean)
                results.setdefault(f"{name} {metric}-stdv", []).append(std)
                agg.append((name, metric, mean, hib, std))
            for cb in cb_after:
                cb(CallbackEnv(cvbooster, params, i, 0, num_boost_round, agg))
    except EarlyStopException as e:
        cvbooster.best_iteration = e.best_iteration + 1
        for k in list(results.keys()):
            results[k] = results[k][: cvbooster.best_iteration]
    if return_cvbooster:
        results["cvbooster"] = cvbooster  # type: ignore[assignment]
    return results
