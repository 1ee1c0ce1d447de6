"""Summarize a paddle_tpu telemetry run directory.

Reads the artifacts dumped by paddle_tpu.observability (metrics.json,
trace.json, steps.jsonl — see docs/OBSERVABILITY.md) and prints a run
summary: step counts, slowest eager ops, cache hit rates, input-starvation
fraction, and the compile-time breakdown.

  PADDLE_TPU_TELEMETRY=1 PADDLE_TPU_METRICS_DIR=/tmp/run python train.py
  python tools/telemetry_report.py /tmp/run [--top 10]
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _load(path):
    if path and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


def _load_jsonl(path):
    rows = []
    if path and os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    return rows


def _counter(metrics, name, default=0.0):
    m = metrics.get(name)
    if not m or not m.get('samples'):
        return default
    return sum(s['value'] for s in m['samples'])


def _gauge_by_label(metrics, name, label):
    out = {}
    m = metrics.get(name)
    for s in (m or {}).get('samples', []):
        out[s['labels'].get(label)] = s['value']
    return out


def _ms(seconds):
    return f"{seconds * 1e3:.3f}ms"


def _rate(hits, misses):
    total = hits + misses
    return f"{hits / total:.1%} ({int(hits)}/{int(total)})" if total \
        else "n/a (no lookups)"


def summarize(metrics, trace, steps, top=10):
    """→ list of report lines (pure; the CLI prints them)."""
    lines = ['# paddle_tpu telemetry report', '']

    # ---- run summary ----
    events = (trace or {}).get('traceEvents', [])
    wall = 0.0
    if events:
        t0 = min(e['ts'] for e in events)
        t1 = max(e['ts'] + e.get('dur', 0.0) for e in events)
        wall = (t1 - t0) / 1e6
    exec_steps = _counter(metrics, 'executor_steps')
    ts_calls = _counter(metrics, 'train_step_calls')
    lines += ['## Run summary',
              f"executor steps:        {int(exec_steps)}",
              f"fused TrainStep calls: {int(ts_calls)}",
              f"traced wall time:      {wall:.3f}s "
              f"({len(events)} trace events, "
              f"{(trace or {}).get('otherData', {}).get('dropped_events', 0)}"
              f" dropped)",
              f"step records:          {len(steps)}",
              '']

    # ---- slowest ops (eager dispatch histograms) ----
    lines.append(f'## Slowest eager ops (top {top} by total dispatch time)')
    rows = []
    for s in (metrics.get('tape_dispatch_seconds') or {}).get('samples', []):
        if s['count']:
            rows.append((s['sum'], s))
    if rows:
        rows.sort(key=lambda r: -r[0])
        lines.append(f"{'op':<28}{'cached':>8}{'calls':>8}{'total':>12}"
                     f"{'mean':>12}{'max':>12}")
        for total, s in rows[:top]:
            lab = s['labels']
            lines.append(
                f"{lab.get('op', '?')[:28]:<28}{lab.get('cached', '?'):>8}"
                f"{s['count']:>8}{_ms(total):>12}"
                f"{_ms(total / s['count']):>12}{_ms(s['max'] or 0):>12}")
    else:
        lines.append('(no eager dispatches recorded)')
    lines.append('')

    # ---- cache hit rates ----
    ek = _gauge_by_label(metrics, 'eager_kernel_cache', 'stat')
    lines += ['## Cache hit rates',
              f"eager kernel cache:    "
              f"{_rate(ek.get('hits', 0), ek.get('misses', 0))}"
              + (f"  [size {int(ek.get('size', 0))}/"
                 f"{int(ek.get('maxsize', 0))}, "
                 f"evictions {int(ek.get('evictions', 0))}, "
                 f"bypasses {int(ek.get('bypasses', 0))}]" if ek else ''),
              f"executor step cache:   "
              f"{_rate(_counter(metrics, 'compile_cache_hits'), _counter(metrics, 'compile_cache_misses'))}",
              f"persistent XLA cache:  "
              f"{_rate(_counter(metrics, 'persistent_cache_hits'), _counter(metrics, 'persistent_cache_misses'))}",
              '']

    # ---- input starvation ----
    wait_total = _counter(metrics, 'dataloader_wait_seconds_total')
    batches = _counter(metrics, 'dataloader_batches')
    lines.append('## Input pipeline')
    if batches:
        frac = wait_total / wall if wall > 0 else float('nan')
        lines += [f"batches:               {int(batches)}",
                  f"total input wait:      {wait_total:.4f}s",
                  f"mean wait / batch:     {_ms(wait_total / batches)}",
                  f"starvation fraction:   {frac:.1%} of traced wall time"]
    else:
        lines.append('(no DataLoader batches recorded)')
    lines.append('')

    # ---- async pipeline (non-blocking fetch handles) ----
    mat = (metrics.get('fetch_materialize_seconds') or {}).get('samples', [])
    mat_n = sum(s['count'] for s in mat)
    lines.append('## Async pipeline')
    if mat_n:
        mat_s = sum(s['sum'] for s in mat)
        passthrough = _counter(metrics, 'executor_feed_passthrough_bytes')
        feed_bytes = _counter(metrics, 'executor_feed_bytes')
        inflight = (metrics.get('executor_inflight_steps') or
                    {}).get('samples', [])
        # host time NOT hidden by the pipeline = D2H materialization waits
        # + input starvation; the rest of the wall clock overlapped device
        # compute with host work — the quantity the K-in-flight window
        # exists to maximize
        blocked = mat_s + wait_total
        lines += [f"materializations:      {int(mat_n)} "
                  f"(total wait {mat_s:.4f}s, "
                  f"mean {_ms(mat_s / mat_n)})",
                  f"in-flight window:      "
                  f"{int(inflight[0]['value']) if inflight else 0} "
                  f"at last export"]
        if feed_bytes:
            lines.append(f"zero-copy staged feeds:"
                         f" {passthrough / feed_bytes:.1%} of feed bytes "
                         f"passed through without a second device_put")
        if wall > 0:
            lines.append(f"overlap fraction:      "
                         f"{max(0.0, 1.0 - blocked / wall):.1%} of traced "
                         f"wall time (1 − (materialize+input waits)/wall)")
    else:
        lines.append('(no FetchHandle materializations recorded — '
                     'synchronous loop; set PADDLE_TPU_ASYNC=1 or '
                     'ExecutionStrategy.num_inflight_steps>1)')
    lines.append('')

    # ---- collectives (quantized + bucketed gradient sync) ----
    sync_calls = _counter(metrics, 'collective_sync_calls')
    buckets = _counter(metrics, 'collective_allreduce_buckets')
    if sync_calls or buckets:
        lines.append('## Collectives')
        if sync_calls:
            by_key = {}
            for s in (metrics.get('collective_sync_calls')
                      or {}).get('samples', []):
                k = (f"{s['labels'].get('path', '?')}"
                     f"/{s['labels'].get('dtype', '?')}")
                by_key[k] = by_key.get(k, 0) + s['value']
            lines.append(
                f"sync calls:            {int(sync_calls)} "
                f"({', '.join(f'{k}: {int(v)}' for k, v in sorted(by_key.items()))})")
            wire = _counter(metrics, 'collective_bytes_on_wire')
            f32eq = _counter(metrics, 'collective_bytes_f32_equiv')
            if wire and f32eq:
                def fmt(b):
                    return f"{b / 2**20:.1f} MiB" if b >= 2**20 \
                        else f"{b / 2**10:.1f} KiB"
                note = '' if f32eq >= wire else \
                    ' — EXPANSION: block padding dominates; tensors this ' \
                    'small should sync at f32'
                lines.append(
                    f"bytes on wire:         {fmt(wire)} vs "
                    f"{fmt(f32eq)} f32-equivalent "
                    f"({f32eq / wire:.2f}x reduction{note})")
            qerr = (metrics.get('collective_quant_rel_error')
                    or {}).get('samples', [])
            qn = sum(s['count'] for s in qerr)
            if qn:
                qs = sum(s['sum'] for s in qerr)
                qmax = max(s['max'] or 0 for s in qerr)
                lines.append(
                    f"quantization error:    mean {qs / qn:.2e} rel/absmax "
                    f"per codec pass, max {qmax:.2e} ({int(qn)} samples)")
        if buckets:
            passes = _gauge_by_label(metrics, 'ir_pass_applied_total',
                                     'pass').get('bucket_allreduce', 0)
            per = buckets / max(passes, 1)
            lines.append(
                f"bucketed all-reduce:   {per:.0f} bucket(s) per lowering "
                f"(PADDLE_TPU_ALLREDUCE_BUCKET_MB caps each)")
            if per > 1:
                lines.append(
                    f"comm overlap ceiling:  {1 - 1 / per:.1%} of gradient "
                    f"comm can overlap backward compute (all but the last "
                    f"bucket dispatch before the backward tail finishes)")
        lines.append('')

    # ---- resilience / goodput ----
    saves = _counter(metrics, 'checkpoint_saves')
    goodput = (metrics.get('goodput_ratio') or {}).get('samples', [])
    lines.append('## Resilience / goodput')
    if saves or goodput:
        ck_bytes = _counter(metrics, 'checkpoint_bytes')
        save_s = (metrics.get('checkpoint_save_seconds')
                  or {}).get('samples', [])
        stall_s = (metrics.get('checkpoint_stall_seconds')
                   or {}).get('samples', [])
        last = (metrics.get('checkpoint_last_step') or {}).get('samples', [])
        lines.append(
            f"checkpoints:           {int(saves)} committed "
            f"({ck_bytes / 2**20:.1f} MiB"
            + (f", latest step {int(last[0]['value'])}" if last else '')
            + ')')
        if save_s and save_s[0]['count']:
            s = save_s[0]
            lines.append(f"background write:      mean "
                         f"{_ms(s['sum'] / s['count'])}, "
                         f"max {_ms(s['max'] or 0)}")
        if stall_s and stall_s[0]['count']:
            s = stall_s[0]
            lines.append(
                f"step-loop stall:       mean {_ms(s['sum'] / s['count'])}, "
                f"max {_ms(s['max'] or 0)} per checkpoint (the async "
                f"writer hides the rest)")
        retries = _counter(metrics, 'checkpoint_retries')
        failures = _counter(metrics, 'checkpoint_failures')
        if retries or failures:
            lines.append(f"IO retries/failures:   {int(retries)} retried, "
                         f"{int(failures)} abandoned")
        if goodput:
            prod = (metrics.get('goodput_productive_seconds')
                    or {}).get('samples', [{'value': 0.0}])[0]['value']
            gwall = (metrics.get('goodput_wall_seconds')
                     or {}).get('samples', [{'value': 0.0}])[0]['value']
            lines.append(f"goodput:               {goodput[0]['value']:.1%} "
                         f"(productive {prod:.1f}s / wall {gwall:.1f}s)")
        restarts = _counter(metrics, 'restarts_total')
        if restarts:
            lines.append(
                f"restarts:              {int(restarts)}, lost "
                f"{int(_counter(metrics, 'restart_lost_steps'))} step(s) / "
                f"{_counter(metrics, 'restart_lost_seconds'):.2f}s of "
                f"replayed work")
        resizes = _counter(metrics, 'elastic_resizes_total')
        resize_lost = (metrics.get('goodput_resize_lost_seconds')
                       or {}).get('samples', [])
        if resizes or (resize_lost and resize_lost[0]['value']):
            lost_s = resize_lost[0]['value'] if resize_lost else 0.0
            reshards = _counter(metrics, 'elastic_reshard_restores')
            lines.append(
                f"elastic resizes:       {int(resizes)} scheduled "
                f"resize(s), {lost_s:.2f}s resize downtime (separate from "
                f"crash loss), {int(reshards)} reshard-on-restore(s)")
        preempt = _counter(metrics, 'preemption_requests')
        faults = _counter(metrics, 'fault_injections')
        if preempt or faults:
            lines.append(f"preemptions/faults:    {int(preempt)} preemption "
                         f"notice(s), {int(faults)} injected fault(s)")
    else:
        lines.append('(no checkpoints recorded — wire a '
                     'resilience.CheckpointManager into the loop; '
                     'docs/RESILIENCE.md)')
    lines.append('')

    # ---- self-healing (supervisor + watchdog, docs/RESILIENCE.md) ----
    detections = _counter(metrics, 'supervisor_detections')
    breaches = _counter(metrics, 'watchdog_breaches')
    if detections or breaches:
        lines.append('## Self-healing')
        if detections:
            by_kind = {
                (s['labels'].get('kind') or '?'): int(s['value'])
                for s in (metrics.get('supervisor_detections')
                          or {}).get('samples', [])}
            lines.append(
                f"detections:            {int(detections)} unhealthy "
                f"step(s) ({', '.join(f'{k}: {v}' for k, v in sorted(by_kind.items()))})")
            skips = _counter(metrics, 'supervisor_skipped_updates')
            rollbacks = _counter(metrics, 'supervisor_rollbacks')
            benign = _counter(metrics, 'supervisor_amp_benign_skips')
            lines.append(
                f"recoveries:            {int(skips)} update(s) dropped, "
                f"{int(rollbacks)} rollback(s), {int(benign)} benign AMP "
                f"overflow skip(s)")
            rec = (metrics.get('supervisor_recovery_seconds')
                   or {}).get('samples', [])
            if rec and rec[0]['count']:
                s = rec[0]
                lines.append(f"rollback restore:      mean "
                             f"{_ms(s['sum'] / s['count'])}, "
                             f"max {_ms(s['max'] or 0)}")
            quarantined = _counter(metrics, 'supervisor_quarantined_batches')
            if quarantined:
                lines.append(f"quarantined:           {int(quarantined)} "
                             f"batch descriptor(s) (quarantine.jsonl)")
        if breaches:
            by_lease = {
                (s['labels'].get('lease') or '?'): int(s['value'])
                for s in (metrics.get('watchdog_breaches')
                          or {}).get('samples', [])}
            lines.append(
                f"WATCHDOG BREACHES:     {int(breaches)} hang(s) "
                f"({', '.join(f'{k}: {v}' for k, v in sorted(by_lease.items()))}), "
                f"{int(_counter(metrics, 'watchdog_stack_dumps'))} stack "
                f"dump(s) written")
        lines.append('')

    # ---- serving tier (router / prefix cache / disagg, docs/SERVING.md) --
    tier_hits = _counter(metrics, 'prefix_cache_hits')
    tier_misses = _counter(metrics, 'prefix_cache_misses')
    routed = _counter(metrics, 'router_requests')
    handoffs = _counter(metrics, 'disagg_handoffs')
    autoscale = _counter(metrics, 'autoscale_decisions')
    if tier_hits or tier_misses or routed or handoffs or autoscale:
        lines.append('## Serving tier')
        if tier_hits or tier_misses:
            saved = _counter(metrics, 'prefix_cache_tokens_saved')
            resident = (metrics.get('prefix_cache_blocks_resident')
                        or {}).get('samples', [])
            lines.append(f"prefix-cache hit rate: "
                         f"{_rate(tier_hits, tier_misses)}")
            lines.append(f"prefill compute saved: {int(saved)} prompt "
                         f"token(s) served from cached KV blocks")
            if resident:
                lines.append(f"cache residency:       "
                             f"{int(resident[0]['value'])} block(s), "
                             f"{int(_counter(metrics, 'prefix_cache_evicted_blocks'))} "
                             f"evicted")
        if routed:
            completed = _counter(metrics, 'router_requests_completed')
            rerouted = _counter(metrics, 'router_requests_rerouted')
            failed = _counter(metrics, 'router_requests_failed')
            lines.append(
                f"router:                {int(routed)} request(s), "
                f"{int(completed)} completed, {int(rerouted)} rerouted "
                f"(failover), {int(failed)} failed in-flight")
            per_replica = _gauge_by_label(metrics,
                                          'router_replica_inflight',
                                          'replica')
            if per_replica:
                load = ', '.join(f'{u}: {int(v)}'
                                 for u, v in sorted(per_replica.items()))
                lines.append(f"per-replica in-flight: {load}")
        if autoscale:
            by_act = {}
            for s in (metrics.get('autoscale_decisions')
                      or {}).get('samples', []):
                key = (f"{s['labels'].get('action', '?')}/"
                       f"{s['labels'].get('trigger', '?')}")
                by_act[key] = by_act.get(key, 0) + int(s['value'])
            detail = ', '.join(f'{k}: {v}'
                               for k, v in sorted(by_act.items()))
            lines.append(f"autoscaler:            {int(autoscale)} "
                         f"decision(s) ({detail})")
            reps = (metrics.get('autoscale_replicas')
                    or {}).get('samples', [])
            routable = (metrics.get('autoscale_replicas_routable')
                        or {}).get('samples', [])
            if reps:
                lines.append(
                    f"tier size:             {int(reps[0]['value'])} "
                    f"replica(s), "
                    f"{int(routable[0]['value']) if routable else 0} "
                    f"routable")
            ttr = (metrics.get('autoscale_time_to_routable_seconds')
                   or {}).get('samples', [])
            if ttr and ttr[0]['count']:
                s = ttr[0]
                lines.append(f"cold-start admission:  mean "
                             f"{s['sum'] / s['count']:.2f}s to routable, "
                             f"max {s['max'] or 0:.2f}s "
                             f"({int(s['count'])} replica(s))")
            dr = (metrics.get('autoscale_drain_seconds')
                  or {}).get('samples', [])
            if dr and dr[0]['count']:
                s = dr[0]
                lines.append(f"drain-then-retire:     mean "
                             f"{s['sum'] / s['count']:.2f}s, "
                             f"max {s['max'] or 0:.2f}s "
                             f"({int(s['count'])} replica(s))")
        if handoffs:
            hb = _counter(metrics, 'disagg_kv_bytes')
            hf = _counter(metrics, 'disagg_handoff_failures')
            lines.append(
                f"disaggregation:        {int(handoffs)} prefill->decode "
                f"handoff(s), {int(hb)} KV byte(s) shipped, "
                f"{int(hf)} failed")
        lines.append('')

    # ---- KV cache (quantized pools + host spill tier, docs/SERVING.md) --
    kv_dtype = (metrics.get('kv_cache_dtype') or {}).get('samples', [])
    kv_hbm = (metrics.get('kv_cache_bytes_in_hbm') or {}).get('samples', [])
    spills = _counter(metrics, 'kv_cache_spill_count')
    reinjects = _counter(metrics, 'kv_cache_reinject_count')
    if kv_dtype or kv_hbm or spills or reinjects:
        lines.append('## KV cache')
        if kv_dtype:
            names = {0: 'f32', 1: 'bf16', 2: 'int8'}
            code = int(kv_dtype[0]['value'])
            lines.append(f"storage dtype:         "
                         f"{names.get(code, f'?({code})')} "
                         f"(PADDLE_TPU_KV_DTYPE)")
        if kv_hbm:
            lines.append(f"bytes in HBM:          "
                         f"{kv_hbm[0]['value'] / 2**20:.3f} MiB "
                         f"(pool pages + row scales)")
        if spills or reinjects:
            sb = _counter(metrics, 'kv_cache_bytes_spilled')
            lines.append(
                f"host spill tier:       {int(spills)} block(s) spilled "
                f"({sb / 2**20:.3f} MiB serialized), "
                f"{int(reinjects)} reinjected on radix hits")
            rs = (metrics.get('kv_cache_reinject_seconds')
                  or {}).get('samples', [])
            if rs and rs[0]['count']:
                s = rs[0]
                lines.append(f"reinject latency:      mean "
                             f"{_ms(s['sum'] / s['count'])}, "
                             f"max {_ms(s['max'] or 0)} per hit path")
        ev = (metrics.get('prefix_cache_evictions') or {}).get('samples', [])
        if ev:
            by_cause = {}
            for s in ev:
                c = s['labels'].get('cause', '?')
                by_cause[c] = by_cause.get(c, 0) + s['value']
            lines.append(
                "evictions by cause:    "
                + ', '.join(f'{c}: {int(v)}'
                            for c, v in sorted(by_cause.items())))
        lines.append('')

    # ---- fleet-wide tier observability (docs/OBSERVABILITY.md) ----
    fleet_scrapes = _counter(metrics, 'router_fleet_scrapes')
    sampled = _counter(metrics, 'trace_requests_sampled')
    ttft = (metrics.get('decode_ttft_seconds') or {}).get('samples', [])
    if fleet_scrapes or sampled or (ttft and ttft[0]['count']):
        lines.append('## Tier (fleet-wide)')
        if fleet_scrapes:
            sfails = _counter(metrics, 'router_scrape_failures')
            lines.append(f"/metrics/fleet:        {int(fleet_scrapes)} "
                         f"aggregation(s), {int(sfails)} failed replica "
                         f"scrape(s)")
        offs = _gauge_by_label(metrics, 'trace_clock_offset_seconds',
                               'replica')
        if offs:
            lines.append(
                "clock offsets:         "
                + ', '.join(f'{r}: {v * 1e3:+.1f}ms'
                            for r, v in sorted(offs.items()))
                + '  (health-handshake estimate, trace_merge.py input)')
        if sampled:
            lines.append(
                f"tracing:               {int(sampled)} sampled "
                f"request(s), "
                f"{int(_counter(metrics, 'trace_spans_recorded'))} "
                f"span(s) recorded")
        if ttft and ttft[0]['count']:
            s = ttft[0]
            lines.append(f"TTFT:                  {s['count']} "
                         f"request(s), mean {_ms(s['sum'] / s['count'])}, "
                         f"max {_ms(s['max'] or 0)}")
        lines.append('')

    # ---- straggler / SLO monitors (docs/OBSERVABILITY.md) ----
    zscores = _gauge_by_label(metrics, 'straggler_zscore', 'host')
    slo_ok = _gauge_by_label(metrics, 'slo_ok', 'slo')
    if zscores or slo_ok:
        lines.append('## Straggler / SLO')
        if zscores:
            flagged = _counter(metrics, 'straggler_flags')
            count = (metrics.get('straggler_count')
                     or {}).get('samples', [])
            lines.append(
                f"straggler monitor:     "
                f"{int(count[0]['value']) if count else 0} host(s) "
                f"currently flagged, {int(flagged)} cumulative detection(s)")
            lines.append(
                "host z-scores:         "
                + ', '.join(f'{h}: {z:+.2f}'
                            for h, z in sorted(zscores.items())))
        if slo_ok:
            burns = _gauge_by_label(metrics, 'slo_breaches', 'slo')
            for clause, ok in sorted(slo_ok.items()):
                state = 'OK' if ok else 'BREACHED'
                lines.append(
                    f"slo {clause:<18} {state} "
                    f"({int(burns.get(clause, 0))} breach evaluation(s))")
        lines.append('')

    # ---- memory plan (analysis/plan.py, docs/ANALYSIS.md) ----
    def _gauge(name):
        s = (metrics.get(name) or {}).get('samples', [])
        return s[0]['value'] if s else None

    peak = _gauge('program_peak_hbm_bytes')
    predicted = _gauge('program_plan_accounted_bytes')
    measured = _gauge('program_measured_hbm_bytes')
    if peak is not None or measured is not None:
        lines.append('## Memory plan')
        if peak is not None:
            lines.append(f"predicted peak HBM:    {peak / 2**20:.3f} MiB "
                         f"(analysis/plan.py, last lowered program)")
        if predicted is not None and measured is not None:
            delta = ((measured - predicted) / predicted
                     if predicted else float('nan'))
            lines.append(
                f"state+feed+fetch:      predicted "
                f"{predicted / 2**20:.3f} MiB vs measured "
                f"{measured / 2**20:.3f} MiB ({delta:+.1%} delta)")
        remat = _gauge('auto_remat_checkpoints')
        if remat:
            planned = _gauge('auto_remat_planned_peak_bytes') or 0
            lines.append(
                f"auto-remat:            {int(remat)} checkpoint(s) "
                f"chosen; post-remat predicted peak "
                f"{planned / 2**20:.3f} MiB "
                f"(PADDLE_TPU_HBM_BUDGET_MB)")
        plan_s = (metrics.get('program_plan_seconds')
                  or {}).get('samples', [])
        if plan_s and plan_s[0]['count']:
            s = plan_s[0]
            lines.append(f"plan time:             "
                         f"{s['count']} plan(s), mean "
                         f"{_ms(s['sum'] / s['count'])}, "
                         f"max {_ms(s['max'] or 0)} (zero tracing)")
        fails = _counter(metrics, 'program_plan_failures')
        if fails:
            lines.append(f"PLAN FAILURES:         {int(fails)} plan "
                         f"attempt(s) raised (best-effort; lowering "
                         f"proceeded)")
        lines.append('')

    # ---- compile-time breakdown ----
    lines.append('## Compile-time breakdown')
    any_compile = False
    for name, label in [
            ('executor_compile_seconds', 'executor lower+compile'),
            ('compile_cache_deserialize_seconds', 'persistent deserialize'),
            ('compile_cache_time_saved_seconds', 'compile time saved')]:
        for s in (metrics.get(name) or {}).get('samples', []):
            if s['count']:
                any_compile = True
                lines.append(f"{label + ':':<23}{s['count']} event(s), "
                             f"total {s['sum']:.3f}s, "
                             f"max {s['max'] or 0:.3f}s")
    build_durs = [e['dur'] / 1e6 for e in events
                  if e['name'] == 'train_step/build']
    if build_durs:
        any_compile = True
        lines.append(f"{'TrainStep build:':<23}{len(build_durs)} event(s), "
                     f"total {sum(build_durs):.3f}s")
    if not any_compile:
        lines.append('(no compiles recorded — fully warm run)')
    lines.append('')

    # ---- anomalies ----
    nonfinite = _counter(metrics, 'nonfinite_detections')
    if nonfinite:
        lines += ['## Anomalies',
                  f"NON-FINITE DETECTIONS: {int(nonfinite)} fetched "
                  f"variable(s) contained NaN/Inf (FLAGS_check_nan_inf)", '']
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('directory', nargs='?',
                    default=os.environ.get('PADDLE_TPU_METRICS_DIR'),
                    help='telemetry artifact dir '
                         '(default: $PADDLE_TPU_METRICS_DIR)')
    ap.add_argument('--metrics', help='explicit metrics.json path')
    ap.add_argument('--trace', help='explicit trace.json path')
    ap.add_argument('--steps', help='explicit steps.jsonl path')
    ap.add_argument('--top', type=int, default=10,
                    help='rows in the slowest-ops table')
    args = ap.parse_args(argv)

    d = args.directory
    mpath = args.metrics or (d and os.path.join(d, 'metrics.json'))
    tpath = args.trace or (d and os.path.join(d, 'trace.json'))
    spath = args.steps or (d and os.path.join(d, 'steps.jsonl'))
    mdoc = _load(mpath)
    if mdoc is None:
        print(f"telemetry_report: no metrics.json found "
              f"(looked at {mpath!r}); run with PADDLE_TPU_TELEMETRY=1 and "
              f"PADDLE_TPU_METRICS_DIR set", file=sys.stderr)
        return 2
    metrics = mdoc.get('metrics', mdoc)
    trace = _load(tpath)
    steps = _load_jsonl(spath)
    print('\n'.join(summarize(metrics, trace, steps, top=args.top)))
    return 0


if __name__ == '__main__':
    sys.exit(main())
