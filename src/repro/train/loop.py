"""The training loop: checkpoint/restart, health monitoring, elastic
re-meshing, async checkpointing, and the numerics-guardrail recovery
ladder — the control plane around train_step.

Observability (obs/): the loop emits TYPED events through a Telemetry
handle — every former ``log_fn(f"[loop] ...")`` call site now writes a
structured record to the sinks AND renders the same human line, so logs
are unchanged while the JSONL artifact gains machine-readable history.
The per-step timing is split honestly: ``device_ms`` (dispatch + device
execution, measured to ``block_until_ready`` on the loss) vs ``fetch_ms``
(the blocking host transfer of the metrics dict) — the formerly-conflated
``dt`` (still reported) is their sum plus host-side loop work.  All device
telemetry rides the ONE existing per-step metrics fetch; the loop adds no
extra host syncs (tests/test_obs.py gates this on the jaxpr).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax

from repro.checkpoint import checkpointing
from repro.core import casts
from repro.core import quant as quant_stats
from repro.data.pipeline import DataConfig, make_batch
from repro.obs.sink import null_telemetry
from repro.obs.trace import Span
from repro.runtime import fault_injection
from repro.runtime.fault_tolerance import ElasticTrainer


def _restore_latest_valid(ckpt_dir, state, shardings, log_fn, tel=None):
    """Newest complete checkpoint that passes the integrity checks; corrupt
    steps (CheckpointCorruptError) are logged and skipped so one poisoned
    shard cannot wedge the rollback path.  Returns (state, step) or None."""
    for s in reversed(checkpointing.completed_steps(ckpt_dir)):
        try:
            st, _ = checkpointing.restore(ckpt_dir, state, step=s,
                                          shardings=shardings)
            return st, s
        except checkpointing.CheckpointCorruptError as e:
            msg = (f"[loop] checkpoint step_{s} failed integrity check "
                   f"({e}); falling back to an older step")
            if tel is not None:
                tel.record("ckpt_corrupt", ckpt_step=s, error=str(e),
                           msg=msg)
                tel.counter("ckpt_corrupt_total").inc()
            log_fn(msg)
    return None


def _ledger_snapshot(tel, fn, state, batch, step, demoted):
    """Cast-ledger snapshot of one step callable, taken abstractly.

    ``casts.record`` fires at Python trace time, so ``jax.eval_shape``
    under an active ledger tallies the full fwd+bwd cast census of this
    step function WITHOUT compiling or running anything.  Called once per
    distinct step callable ("per recompile": the fp8 step on first use,
    the bf16 fallback step on first demotion)."""
    try:
        with casts.ledger() as led:
            jax.eval_shape(fn, state, batch)
        tel.record(
            "cast_ledger", step=step, demoted=bool(demoted),
            fn=getattr(fn, "__name__", type(fn).__name__),
            activation_casts=led.activation_casts(),
            fused_casts=led.fused_casts(), total=led.total(),
            by_tag={f"{k}:{t}": n
                    for (k, t), n in sorted(led.by_tag().items())})
    except Exception as e:      # snapshot is best-effort; never break a step
        tel.record("cast_ledger_error", step=step, error=str(e))


def run(train_step: Callable, state, data_cfg: DataConfig, *,
        n_steps: int, ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
        log_every: int = 10, elastic: Optional[ElasticTrainer] = None,
        grad_accum: int = 1, fail_injector: Optional[Callable] = None,
        restore_shardings=None, log_fn=print, guard_policy=None,
        fallback_step: Optional[Callable] = None,
        fault_plan: Optional[fault_injection.FaultPlan] = None,
        telemetry=None):
    """Runs `n_steps`, restarting from the latest checkpoint if present.
    `fail_injector(step)` lets tests simulate host failures/stragglers.
    `restore_shardings` (optional pytree of NamedSharding matching `state`,
    e.g. launch/sharding.dist_state_specs for ZeRO-1 flat state) re-shards
    on restore — restart onto a different DP mesh size just works because
    the checkpoint holds the full logical arrays.

    guard_policy (train/guards.GuardPolicy) drives the recovery ladder off
    the 'guard_flags' metric a guarded train_step emits: skip-step (the
    previous state is still a live reference — discard the update, replay
    nothing), rollback to the last VALID checkpoint after K consecutive
    strikes (rewinding `step` so the data pipeline replays those batches),
    and demotion to `fallback_step` (a bf16-recipe step built with the
    same GuardPlan) for a bounded window before re-promoting.

    fault_plan (runtime/fault_injection.FaultPlan) schedules deterministic
    faults: numeric ones are baked into per-spec jit traces when
    `train_step` is a FaultStepper (`fault_plan.wrap(raw_step)`), host
    failures flip the HealthMonitor, and disk faults corrupt checkpoint
    shards on the way in.

    telemetry (obs/sink.Telemetry) collects typed events, per-step metric
    samples (riding the existing metrics fetch — zero extra host syncs),
    host-side span timings, and cast-ledger snapshots.  None -> a null
    handle: identical behavior, nothing kept."""
    tel = telemetry if telemetry is not None else null_telemetry()

    def _event(kind, msg, **fields):
        # typed record + the VERBATIM human line (tests grep these)
        tel.record(kind, msg=msg, **fields)
        log_fn(msg)

    start = 0
    if ckpt_dir is not None and checkpointing.latest_step(ckpt_dir) is not None:
        res = _restore_latest_valid(ckpt_dir, state, restore_shardings,
                                    log_fn, tel)
        if res is not None:
            state, rstep = res
            start = rstep + 1
            _event("ckpt_restore", f"[loop] restored checkpoint step={rstep}",
                   ckpt_step=rstep)

    if guard_policy is not None and getattr(guard_policy, "telemetry",
                                            None) is None:
        guard_policy.telemetry = tel

    history = []
    pending_save = None
    ledgered = set()        # id() of step callables already snapshot

    def _join_pending():
        nonlocal pending_save
        if pending_save is not None:
            pending_save.join()     # re-raises a failed background write
            pending_save = None

    step = start
    while step < n_steps:
        t0 = time.monotonic()
        if fault_plan is not None and ckpt_dir is not None:
            disk = fault_plan.disk_for(step)
            if disk is not None:
                _join_pending()
                poisoned = fault_injection.apply_disk_fault(disk, ckpt_dir)
                _event("disk_fault",
                       f"[loop] injected {disk.kind} at step {step} "
                       f"(checkpoint step_{poisoned})",
                       step=step, fault=disk.kind, ckpt_step=poisoned)
        batch = make_batch(data_cfg, step)
        if grad_accum > 1:
            batch = jax.tree.map(
                lambda a: a.reshape(grad_accum, a.shape[0] // grad_accum,
                                    *a.shape[1:]), batch)
        demoted = guard_policy is not None and fallback_step is not None \
            and guard_policy.demoted(step)
        fn = fallback_step if demoted else train_step
        if hasattr(fn, "for_step"):     # FaultStepper: per-spec jit cache
            fn = fn.for_step(step)
        if tel.enabled and id(fn) not in ledgered:
            ledgered.add(id(fn))
            _ledger_snapshot(tel, fn, state, batch, step, demoted)
        prev_state = state
        # the honest split of the old conflated `dt`: device span covers
        # dispatch + device execution (to data-ready), fetch span the
        # blocking device->host copy of the metrics dict — still the loop's
        # ONE per-step fetch (guard flags + quant stats ride along).
        with Span("train/device_step") as sp_dev:
            state, metrics = fn(state, batch)
            jax.block_until_ready(metrics)
        with Span("train/host_fetch") as sp_fetch:
            host_metrics = jax.device_get(metrics)
        loss = float(host_metrics["loss"])
        dt = time.monotonic() - t0
        history.append({"step": step, "loss": loss, "dt": dt,
                        "device_ms": sp_dev.ms, "fetch_ms": sp_fetch.ms})
        if "grad_norm" in host_metrics:
            history[-1]["grad_norm"] = float(host_metrics["grad_norm"])

        if tel.enabled:
            values = {"loss": loss}
            for k in ("grad_norm", "quant_sat_frac", "quant_flush_frac",
                      "guard_flags"):
                if k in host_metrics:
                    values[k] = float(host_metrics[k])
            extra = {}
            sv = host_metrics.get("quant_site_stats")
            if sv is not None:
                sites = {}
                for i, name in enumerate(quant_stats.STAT_SITES):
                    sat, flush = float(sv[i][0]), float(sv[i][1])
                    sites[name] = {"sat": sat, "flush": flush}
                    tel.gauge("quant_sat_frac",
                              labels={"site": name}).set(sat)
                    tel.gauge("quant_flush_frac",
                              labels={"site": name}).set(flush)
                extra["quant_sites"] = sites
            if demoted:
                extra["demoted"] = True
            tel.step(step, values,
                     spans={"device": sp_dev.ms, "fetch": sp_fetch.ms,
                            "total": dt * 1e3},
                     extra=extra)

        if guard_policy is not None:
            flags = int(host_metrics.get("guard_flags", 0))
            have_ckpt = ckpt_dir is not None and \
                bool(checkpointing.completed_steps(ckpt_dir))
            verdict = guard_policy.observe(step, flags, log_fn,
                                           can_rollback=have_ckpt)
            if verdict.skip:
                state = prev_state      # discard the poisoned update
                if verdict.rollback and have_ckpt:
                    _join_pending()
                    res = _restore_latest_valid(ckpt_dir, state,
                                                restore_shardings, log_fn,
                                                tel)
                    if res is not None:
                        state, rstep = res
                        _event("rollback",
                               f"[loop] rolled back to step {rstep}; "
                               f"replaying from step {rstep + 1}",
                               step=step, ckpt_step=rstep)
                        step = rstep + 1
                        continue
                step += 1
                continue

        if elastic is not None:
            if fault_plan is not None:
                hf = fault_plan.host_for(step)
                if hf is not None:
                    fault_injection.apply_host_fault(hf, elastic)
                    _event("host_fault",
                           f"[loop] injected host_failure "
                           f"host={hf.site or 0} at step {step}",
                           step=step, host=hf.site or 0)
            if fail_injector is not None:
                fail_injector(step, elastic)
            elastic.step_report(0, dt)
            remesh, reassign = elastic.plan_step()
            if remesh:
                _event("remesh",
                       f"[loop] host failure at step {step}: shrinking to "
                       f"{elastic.n_data_shards} data shards; restoring "
                       f"checkpoint and continuing",
                       step=step, n_data_shards=elastic.n_data_shards)
                if ckpt_dir is not None:
                    # join FIRST: an async save still in flight (e.g. from
                    # two steps ago) must land before we look for the
                    # newest checkpoint, or the rewind silently no-ops
                    _join_pending()
                if ckpt_dir is not None and \
                        checkpointing.latest_step(ckpt_dir) is not None:
                    res = _restore_latest_valid(ckpt_dir, state,
                                                restore_shardings, log_fn,
                                                tel)
                    if res is not None:
                        state, rstep = res
                        # rewind so the optimizer steps between the
                        # checkpoint and the failure are REPLAYED (the data
                        # pipeline is a pure function of step, so the
                        # survivors re-derive exactly those batches)
                        _event("rewind",
                               f"[loop] rewound to step {rstep + 1} after "
                               f"remesh (was {step + 1})",
                               step=step, resume_step=rstep + 1)
                        step = rstep + 1
                        continue
            elif reassign:
                _event("reassign",
                       f"[loop] stragglers reassigned: {reassign}",
                       step=step, assignments=list(reassign))

        if step % log_every == 0:
            _event("progress",
                   f"[loop] step={step} loss={loss:.4f} "
                   f"gnorm={float(host_metrics.get('grad_norm', 0)):.3f} "
                   f"dt={dt*1e3:.0f}ms",
                   step=step, loss=loss, dt_ms=dt * 1e3)
        if ckpt_dir is not None and step % ckpt_every == 0 and step > 0:
            _join_pending()
            pending_save = checkpointing.save(ckpt_dir, step, state,
                                              async_=True)
            tel.record("ckpt_save", step=step)
            tel.counter("ckpt_saves_total").inc()
        step += 1
    _join_pending()
    tel.flush()
    return state, history
