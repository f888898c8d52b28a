"""Process-global counter/gauge registry.

Reference analogue: the fleet metric tables and the profiler's aggregate
stats (SURVEY §"Metrics / logging / observability") — named monotonically
increasing counters that the runtime bumps on every hot-path event, cheap
enough to stay always-on.  Unlike host-tracer spans (gated by
``FLAGS_host_trace_level``), counters are never disabled: they are the
substrate perf contracts are asserted against (``scripts/bench_smoke.py``,
``scripts/check_counters.py``).

Well-known names (see README "Observability" for the full table):

  jit.steps / jit.traces / jit.cache_hits / jit.cache_misses
  jit.hydrates / jit.syncs
  jit.host.dispatches (XLA launches: steps/K under fused_steps=K)
  jit.fused_windows / jit.fused_fallback_steps
  jit.host.layer_state / jit.host.bind_layer_state /
  jit.host.optimizer_state / jit.host.bind_optimizer_state
  jit.nan_inf_checks / jit.nan_inf_hits (FLAGS_check_nan_inf sweeps)
  jit.devicetime.dispatches (dispatches noted by the device-time ledger
      while FLAGS_device_time_sample > 0; 0 when sampling is off)
  jit.devicetime.sampled_syncs (explicit block-until-ready fences the
      sampler paid — exactly ceil(dispatches / N) over a window started
      by devicetime.reset(); the sync-budget gate's devicetime line)
  static.runs / static.compiles / static.traces
  io.device_put_calls / io.device_put_bytes
  io.stack_windows / io.stack_batches
  io.reader_ns / io.prefetch_stall_ns / io.queue_wait_ns
  dist.collectives / dist.<op> / dist.mp_collectives
  dist.collective_launches (host-issued collective dispatches)
  dist.device_put_sharded_bytes (bytes placed via sharded device_put:
      mesh hydrate + data-parallel batch/window staging)
  optimizer.steps
  serving.requests / serving.prefill_batches / serving.decode_steps
  serving.decode_tokens / serving.evictions / serving.evictions.<reason>
  serving.decode.sampled_steps (decode launches with a running
      do_sample row: the ones whose sampling tail ran the filters)
  serving.decode.upload_steps (decode launches that uploaded at least
      one per-slot operand: a slot changed hands since the launch
      before; the others took every operand from the device)
  serving.decode.overlapped_steps (decode launches enqueued while the
      launch before them was not yet read back: the paged engine reads
      launch N's tokens after it enqueues N+1; registered at 0 by the
      block-decoding and speculative engines, which never overlap)
  serving.moe.assignments / serving.moe.tokens ((token, held expert)
      pairs computed / tokens routed by an expert model's layers; kept on
      the device by the serving programs, fetched by
      LLMEngine.step_state() and published by the model's moe_load()
      alone) / serving.moe.load_max_over_mean
      (gauge: busiest held expert over the mean, as last read)
  kernels.moe.grouped_mm.pallas / kernels.moe.grouped_mm.xla (grouped
      products of the held experts traced with the weight-stationary
      Pallas kernel / with the jax.lax.ragged_dot twin: the choice is
      static, so it is counted where programs are built, one a traced
      call, two an expert layer's trace; a steady state counts nothing)
  serving.diffusion.row_passes / serving.diffusion.commits /
      serving.diffusion.revealed (a block-decoding engine alone,
      serving/block_decode.py: running rows of each decode launch, one
      pass of a row's block each / blocks committed / masked positions
      revealed; serving.decode_tokens counts tokens EMITTED there)
  kernels.window_attention.prefill.pallas /
  kernels.window_attention.prefill.xla (a prefill chunk's attention over
      its band traced with the Pallas kernel / with the XLA twin, one a
      traced call; counted where chunk programs are built, a steady state
      counts nothing)
  kernels.selective_scan.pallas / kernels.selective_scan.xla (a Mamba
      layer's selective scan traced with the Pallas kernel / with the
      XLA twin, one a traced call; counted where programs are built, a
      steady state counts nothing)
  serving.kv.window_blocks_recycled (an engine of a model with window
      layers alone: window-ring entries a row took over as it passed the
      window, counted at each chunk and each decode read-back)
  serving.retraces (serving program compiles; 0 in steady state)
  serving.queue_wait_ns
  serving.deadline_expired (queued past-deadline, evicted pre-prefill)
  serving.request_errors (poisoned requests contained to reason "error")
  serving.slot_occupancy / serving.prefill_programs (gauges)
  serving.fleet.dispatched / serving.fleet.shed (SLO load shedding)
  serving.fleet.retried (fault-driven requeues, at-most-once re-prefill)
  serving.fleet.respawns / serving.fleet.replica_deaths[.<reason>]
  serving.fleet.heartbeat_misses (stall detector trips)
  serving.fleet.completed[.<reason>] / serving.fleet.replayed_tokens
  serving.fleet.warmup_requests / serving.fleet.monitor_errors
  serving.fleet.replay_divergence (resumed stream disagreed with replay)
  serving.fleet.prefix_routed (dispatches won by prefix-cache affinity)
  serving.fleet.lost (admitted request without terminal state; MUST be 0)
  serving.fleet.replicas / serving.fleet.decode_tps (gauges)
  serving.fleet.health_shed (admissions refused because the health
      plane's admission level is critical; also counted under .shed)
  serving.fleet.migrate.requests (prefill→decode KV hand-offs completed)
  serving.fleet.migrate.blocks_copied (blocks device-copied by
      migrations: owned, non-prefix-shared blocks ONLY)
  serving.fleet.migrate.blocks_shared (blocks adopted from the
      destination's radix tree by refcount transfer — never copied)
  serving.fleet.migrate.tokens (KV tokens handed off)
  serving.fleet.migrate.deferred (hand-offs parked on decode-side
      backpressure; the request stays held on its source, KV intact,
      and the migration retries next scheduler tick)
  serving.fleet.migrate.dropped (migrations severed by the
      kv_migrate_drop fault site; request replays, nothing lost)
  serving.fleet.migrate.failed (migrations aborted: no decode capacity
      or destination pool exhausted; request replays)
  serving.autoscale.decisions[.<action>] (autoscaler actions taken:
      disaggregate / grow_prefill / grow_decode / retire)
  serving.autoscale.flips.to_prefill / serving.autoscale.flips.to_decode
      (replica role changes, by direction)
  serving.autoscale.spawns / serving.autoscale.retires (fleet-size
      changes the autoscaler made)
  serving.autoscale.prefill_replicas / serving.autoscale.decode_replicas
      (gauges: the live role split; both 0 in a unified fleet)
  serving.kv.prefix_hits / serving.kv.prefix_misses /
  serving.kv.prefix_hit_tokens (paged radix prefix-cache outcomes)
  serving.kv.cow_copies (copy-on-write partial-block adoptions)
  serving.kv.blocks_evicted / serving.kv.pool_exhausted
  serving.kv.prefill_chunks (chunked-prefill program launches)
  serving.kv.blocks_used (gauge: block-pool blocks currently owned)
  serving.kv.quant.prefill_tokens / serving.kv.quant.decode_tokens
      (tokens quantized on insert into an int8/fp8 KV arena)
  serving.kv.quant.arena_bytes / serving.kv.quant.bytes_saved (gauges:
      quantized arena+scales footprint, and savings vs the model dtype)
  serving.kv.tier.spilled_blocks / serving.kv.tier.restored_blocks
      (host-RAM KV tier traffic: device blocks demoted to pinned host
      buffers, and host entries paged back into the arena)
  serving.kv.tier.spill_drops (host copies discarded: tier LRU
      overflow, request teardown while spilled, or the kv_spill_drop
      fault; the affected tokens replay by deterministic re-prefill)
  serving.kv.tier.readopted (host-resident prefix nodes flipped back to
      device residency for free because a donor carried a live copy)
  serving.kv.tier.host_blocks (gauge: tier entries currently resident)
  serving.kv.host_arena_bytes (gauge: total pinned host bytes ever
      allocated for the tier — flat once the reuse pool is warm)
  serving.kv.host_buf_reuse (spill/restore buffers served from the
      reuse pool instead of a fresh allocation)
  serving.spec.drafted / serving.spec.accepted / serving.spec.rejected
      (speculative decoding proposal outcomes; accepted + rejected ==
      drafted, every scheduler round)
  serving.spec.draft_steps / serving.spec.verify_steps (speculative
      dispatches: K+1 draft launches + ONE verify launch per round)
  serving.spec.draft_prefill_chunks (draft-namespace chunked prefill)
  serving.spec.draft_starved (rounds a slot drafted nothing because the
      pool could not cover its draft-table growth; throughput-only)
  serving.spec.rollback_blocks (draft blocks released by post-verify
      block-table truncation — rejection rollback, no device copies)
  serving.spec.acceptance / serving.spec.yield (gauges: acceptance-rate
      EMA and emitted-tokens-per-round-per-slot EMA)
  serving.fleet.spec_acceptance (gauge: drafted-weighted fleet mean)
  serving.mesh.spec_degraded (sharding specs soft-degraded to
      replicated by the StateArena — e.g. nh not divisible by mp; 0
      when every declared leaf sharded as ruled)
  serving.arena.program_hits / serving.arena.program_misses (StateArena
      compile-cache outcomes; misses only at warmup, 0 in steady state)
  serving.arena.program_evictions (programs dropped by the arena LRU
      cap) / serving.arena.program_rebuilds (evicted keys compiled
      AGAIN — the retrace-accounting signal; MUST be 0 in steady state)
  serving.arena.programs (gauge: live programs the arena fronts)
  serving.adapter.hits / serving.adapter.misses (multi-tenant LoRA
      acquisitions served by a resident slot vs needing a page-in)
  serving.adapter.loads (tenant factor page-ins: ONE cached donated
      dispatch each — eviction-then-reuse never retraces)
  serving.adapter.evictions (refcount-0 LRU tenants displaced to make
      room for a cold page-in)
  serving.adapter.arena_exhausted (admissions deferred because every
      adapter slot is referenced by a running request)
  serving.adapter.load_drops (page-ins severed by the adapter_load_drop
      fault BEFORE any slab write; the request defers, refcounts
      reconcile, no tenant ever sees another tenant's weights)
  serving.adapter.resident (gauge: tenants currently device-resident)
  serving.adapter.arena_bytes (gauge: A/B slab HBM footprint per chip)
  serving.fleet.adapter_routed (dispatches won by tenant affinity — the
      winning replica already held the request's adapter)
  kernels.paged.pallas_programs / kernels.paged.xla_fallbacks
      (trace-time: paged decode programs compiled with the Pallas
      block-table walk — every TPU engine whose K/V slabs are whole
      tiles — vs the plain-XLA gather twin; 0 in steady state)
  kernels.flash.reference_calls (trace-time: flash/ring attention calls
      that took the jnp reference instead of the Pallas kernel — off-TPU,
      or a sequence that does not tile by 128; chip_smoke.py asserts 0)
  resilience.saves / resilience.save_ms / resilience.restores
  resilience.resharded_restores (restores onto a different mesh shape)
  resilience.retries / resilience.corrupt_detected
  resilience.recoveries / resilience.recovered.<ExcType>
  resilience.save_failures / resilience.gc_removed
  resilience.faults_injected / resilience.faults_injected.<site>
  io.skipped_batches (replay-to-offset batches skipped on resume)
  train.steps_accum / train.loss_mean / train.grad_norm_mean /
  train.skip_steps (gauges: donated in-graph metric accumulator,
      harvested by metrics_flush at sync boundaries)
  flight.dumps / flight.dumps.<reason> (postmortem bundles written)
  program.<name>.<field> (gauges: per-compiled-program HBM bytes /
      compile seconds / FLOPs under FLAGS_device_telemetry; the
      device-time ledger adds device_time_mean_ms / device_time_samples
      / tflops / mfu / hbm_gbps / ai under FLAGS_device_time_sample)
  serving.fleet.slow_decode_stalls (injected slow_decode stall beats)
  trace.started / trace.finished / trace.spans (request tracing; all 0
      when FLAGS_request_trace_sample=0 — the zero-overhead-off gate)
  trace.kept / trace.kept.head / trace.kept.tail / trace.dropped
      (retention split: head sampling vs tail keep-always on
      deadline/error/retried)
  goodput.fraction / goodput.accounted / goodput.wall_ns /
  goodput.<bucket>_ns (gauges: GoodputLedger.report() wall-clock split)
  analysis.audits (programs AOT-audited under FLAGS_program_audit)
  analysis.findings / analysis.findings.<rule> (audit invariant
      violations: donation-dropped / host-callback / dynamic-shape /
      f64-promotion / collective-budget / hbm-budget / trace-error)
  analysis.collectives_in_graph (allowlisted collective ops found in
      audited mesh programs' compiled HLO — the in-graph-collectives-
      only proof: > 0 with dist.collective_launches == 0 means every
      cross-chip reduction is GSPMD-inserted, none host-launched)
  health.ticks (HealthMonitor snapshot ticks; 0 when FLAGS_health off —
      the zero-overhead-off gate of the health plane)
  health.alerts.fired / health.alerts.fired.<rule> (0->1 alert
      transitions: one flight dump per fire, deduped while firing)
  health.alerts.resolved / health.alerts.resolved.<rule>
  health.admission_level (gauge: 0 ok / 1 degraded / 2 critical — the
      recommendation Router/fleet stats()["health"] expose)

Latency *distributions* (serving.ttft_ns, serving.itl_ns,
serving.queue_wait_ns, io.prefetch_stall_ns, resilience.save_ms, ...)
live in profiler.metrics histograms; the migrated ``*_ns``/``*_ms``
names above keep ticking here as plain sums for back-compat.
Multi-tenant serving adds per-tenant-bucket isolation histograms
(serving.ttft_ns.tenant.<bucket> and serving.itl_ns.tenant.<bucket>,
bucket = "base" or a crc32 hash bucket "t<n>") — the health plane's
noisy_neighbor watchdog reads their windowed p95s.
"""

from __future__ import annotations

import threading

_LOCK = threading.Lock()
_COUNTERS: dict[str, float] = {}
_GAUGES: dict[str, float] = {}


def inc(name: str, value=1):
    """Bump a monotonic counter (thread-safe)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def set_gauge(name: str, value):
    """Set a point-in-time gauge (last-write-wins)."""
    with _LOCK:
        _GAUGES[name] = value


def get(name: str, default=0):
    return _COUNTERS.get(name, _GAUGES.get(name, default))


def names():
    with _LOCK:
        return sorted(set(_COUNTERS) | set(_GAUGES))


def snapshot() -> dict:
    """Copy of every counter and gauge — the unit of delta accounting."""
    with _LOCK:
        out = dict(_COUNTERS)
        out.update(_GAUGES)
        return out


def delta(before: dict, after: dict | None = None) -> dict:
    """Per-name movement between two snapshots (``after`` defaults to now).
    Names absent from ``before`` count from 0; zero deltas are dropped."""
    if after is None:
        after = snapshot()
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0)
        if d != 0:
            out[k] = d
    return out


def reset(name: str | None = None):
    """Zero one counter/gauge, or all of them (test isolation)."""
    with _LOCK:
        if name is None:
            _COUNTERS.clear()
            _GAUGES.clear()
        else:
            _COUNTERS.pop(name, None)
            _GAUGES.pop(name, None)


def allreduce(group=None) -> dict:
    """Fleet view: element-wise sum of every rank's counters (reference: the
    allreduce'd fleet metric tables).  Single-process: a plain snapshot."""
    local = snapshot()
    try:
        from ..distributed import get_world_size
        if get_world_size() <= 1:
            return local
    except Exception:
        return local
    from ..distributed.communication import all_gather_object
    gathered: list = []
    all_gather_object(gathered, local, group=group)
    out: dict = {}
    for snap in gathered:
        for k, v in snap.items():
            out[k] = out.get(k, 0) + v
    return out
