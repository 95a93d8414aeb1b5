"""Single declared registry of every Prometheus series the server emits.

`/metrics` (server/routers/metrics.py) derives its `# TYPE` lines from
this table, and the MET01 static checker verifies every emission site
against it: tracer counters (`tracer.inc("name", **labels)` becomes
`dstack_tpu_<name>_total`), hand-emitted gauges, and literal metric
names anywhere in the tree must appear here with exactly the declared
label set. Because it is one dict, a duplicate name with two differing
label sets — the bug class that motivated MET01: the run resilience
counters and the tracer event counters once shared
`dstack_tpu_run_preemptions_total` with different labels — cannot be
expressed at all.

Keep entries sorted; the checker also enforces counter suffix naming
(`_total` / `_sum` / `_count`).

Histograms are declared once under their BASE name with type
`"histogram"`; the `_bucket` / `_sum` / `_count` series (and the
reserved `le` label) are derived at exposition time — declaring them by
hand, or declaring `le`, is a MET01 violation. `histogram_base()`
resolves a derived name back to its declaration.
"""

from typing import Dict, Optional, Tuple

PREFIX = "dstack_tpu_"

# name -> (type, label names). Label order here is documentation; the
# exposition sorts labels alphabetically.
METRICS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    # Per-run resilience totals, sourced from the runs.resilience JSON
    # column (survive server restarts).
    "dstack_tpu_run_clean_drains_total": ("counter", ("project", "run")),
    "dstack_tpu_run_elastic_resizes_total": ("counter", ("project", "run")),
    "dstack_tpu_run_preemptions_total": ("counter", ("project", "run")),
    "dstack_tpu_run_restarts_total": ("counter", ("project", "run")),
    "dstack_tpu_run_scheduler_preemptions_total": ("counter", ("project", "run")),
    "dstack_tpu_run_steps_lost_total": ("counter", ("project", "run")),
    # In-process tracer event counters (reset on restart). Deliberately
    # named *_events_total so they can never collide with the DB-sourced
    # totals above.
    "dstack_tpu_run_clean_drain_events_total": ("counter", ("run",)),
    "dstack_tpu_run_elastic_resize_events_total": ("counter", ("run",)),
    "dstack_tpu_run_preemption_events_total": ("counter", ("run",)),
    "dstack_tpu_run_restart_events_total": ("counter", ("run",)),
    "dstack_tpu_run_scheduler_preemption_events_total": ("counter", ("run",)),
    # Background FSM tick accounting.
    "dstack_tpu_tick_rows_scanned_total": ("counter", ("processor",)),
    "dstack_tpu_tick_rows_stepped_total": ("counter", ("processor",)),
    # Sharded FSM (PR 11, services/shard_map.py): per-replica lease-shard
    # ownership, rebalance churn (acquired/released/lost), and processor
    # step failures — a crash-looping processor shows up here, not just
    # in logs.
    "dstack_tpu_fsm_shard_rebalances_total": ("counter", ("action",)),
    "dstack_tpu_fsm_shards_owned": ("gauge", ()),
    "dstack_tpu_fsm_step_errors_total": ("counter", ("namespace",)),
    # Failure-isolated serving tier (PR 9). Route staleness is seconds
    # since the data-plane worker's last successful epoch sync (0 when the
    # control plane is reachable); lease takeovers count expired foreign
    # leases stolen by this replica's ClaimLocker — the replica-kill chaos
    # drill asserts it goes positive on the survivor.
    "dstack_tpu_dataplane_route_staleness_seconds": ("gauge", ()),
    "dstack_tpu_lease_renewal_failures_total": ("counter", ("namespace",)),
    "dstack_tpu_lease_takeovers_total": ("counter", ("namespace",)),
    # Per-run lifecycle stage durations (services/run_events.py): the
    # time each stage of the submit -> first-step/first-token path took,
    # observed when the NEXT stage event lands. Quantiles come from the
    # bucket ladder instead of EWMAs.
    "dstack_tpu_run_stage_seconds": ("histogram", ("stage",)),
    # Proxy data plane (services/proxy_pool.py + routing_cache.py):
    # request/error counters per traffic kind (service | model), pooled
    # client gauge, routing-cache hit rate, and the TTFB histogram
    # accumulated in the pool (bucket/sum/count derived at exposition).
    "dstack_tpu_proxy_pool_connections": ("gauge", ()),
    "dstack_tpu_proxy_requests_total": ("counter", ("kind",)),
    "dstack_tpu_proxy_routing_cache_hit_rate": ("gauge", ()),
    "dstack_tpu_proxy_ttfb_seconds": ("histogram", ("kind",)),
    "dstack_tpu_proxy_upstream_errors_total": ("counter", ("kind",)),
    # Podracer RL workload (workloads/rl.py `rl_prometheus_metrics`,
    # exposed by the drill's learner /metrics): rollout throughput,
    # learner cadence, and the weight-refresh channel. weight_refreshes
    # is role-split (learner publishes vs actor adoptions) so a stuck
    # refresh path shows as the two legs diverging; weight_epoch{actor}
    # is the MINIMUM across live actors (the laggard), with per-actor
    # lag in refresh_staleness_epochs. The actor label is gang-rank
    # sized — bounded by the run's width, never client-chosen.
    "dstack_tpu_rl_env_steps_total": ("counter", ()),
    "dstack_tpu_rl_episodes_total": ("counter", ()),
    "dstack_tpu_rl_gang_resizes_total": ("counter", ()),
    "dstack_tpu_rl_learn_step_seconds": ("histogram", ()),
    "dstack_tpu_rl_learn_steps_total": ("counter", ()),
    "dstack_tpu_rl_refresh_seconds": ("histogram", ()),
    "dstack_tpu_rl_refresh_staleness_epochs": ("gauge", ("actor",)),
    "dstack_tpu_rl_reward_mean": ("gauge", ()),
    "dstack_tpu_rl_rollout_seconds": ("histogram", ()),
    "dstack_tpu_rl_weight_epoch": ("gauge", ("role",)),
    "dstack_tpu_rl_weight_refreshes_total": ("counter", ("role",)),
    # Prefix-affinity fleet routing (PR 18, services/routing_cache.py):
    # affinity pick outcomes (hit = the scoring pass chose the replica,
    # miss = no fresh sketch matched or the imbalance escape hatch
    # rejected the winner), the per-decision winning-score distribution
    # (expected matched blocks + adapter bonus, freshness-decayed), and
    # the age of the oldest gossiped sketch — the staleness bound the
    # one-poll gossip cadence promises.
    "dstack_tpu_routing_affinity_hits_total": ("counter", ()),
    "dstack_tpu_routing_affinity_misses_total": ("counter", ()),
    "dstack_tpu_routing_affinity_score": ("histogram", ()),
    "dstack_tpu_routing_sketch_age_seconds": ("gauge", ()),
    # Cold-start fast path (PR 20, workloads/compile_cache.py): programs
    # retrieved from vs written to the persistent XLA compile cache.
    # hits+misses move only when the persistent cache is enabled; a warm
    # boot shows hits ~= the engine's program count and a near-zero
    # compile stage (docs/guides/serving-tuning.md, "cold start").
    "dstack_tpu_compile_cache_hits_total": ("counter", ()),
    "dstack_tpu_compile_cache_misses_total": ("counter", ()),
    # Seconds inside backend compilation (retrievals report their own,
    # much smaller, durations) — the cost the cache removes; wall-clock
    # warmup also pays tracing/lowering, which it cannot.
    "dstack_tpu_compile_seconds_total": ("counter", ()),
    # Serving engine (workloads/serving.py `prometheus_metrics`, exposed
    # by the native model server's /metrics): paged-KV pool occupancy,
    # prefix-cache effectiveness, chunked-prefill accounting, and the
    # admission counters behind the TTFT histogram.
    "dstack_tpu_serving_admitted_total": ("counter", ()),
    # Multi-tenant LoRA serving (workloads/lora_serving.py + the native
    # server's QoS layer): adapter-pool occupancy plus per-tenant
    # request/shed counters and TTFT. The tenant label is
    # bounded-cardinality by construction (dataplane/qos.TenantLabels
    # collapses tenants past the cap into "overflow") — client-chosen
    # ids never mint unbounded series.
    "dstack_tpu_serving_adapters_loaded": ("gauge", ()),
    # Ragged paged attention: jitted-program dispatches per
    # implementation (path = "pallas" | "lax_ragged").
    "dstack_tpu_serving_attn_dispatch_total": ("counter", ("path",)),
    # Work at the scheduler's boundary: steps of every launched decode
    # chunk or speculation round, the same times the slots live at
    # launch, and the tokens those steps emitted; the blocks the live
    # slots' contexts filled at those steps against the block table's
    # columns (what paged attention had to read against what it spans).
    # ... and layer by layer: live blocks x layers, the columns the
    # layers' attention walks visit, and window layers' blocks behind
    # their slot's window (held by the one pool, read by no later step).
    "dstack_tpu_serving_decode_attended_blocks_total": ("counter", ()),
    "dstack_tpu_serving_decode_layer_blocks_total": ("counter", ()),
    "dstack_tpu_serving_decode_live_blocks_total": ("counter", ()),
    "dstack_tpu_serving_decode_slot_steps_total": ("counter", ()),
    "dstack_tpu_serving_decode_steps_total": ("counter", ()),
    "dstack_tpu_serving_decode_table_columns_total": ("counter", ()),
    "dstack_tpu_serving_decode_tokens_total": ("counter", ()),
    "dstack_tpu_serving_decode_window_dead_blocks_total": ("counter", ()),
    "dstack_tpu_serving_kv_blocks_cached": ("gauge", ()),
    "dstack_tpu_serving_kv_blocks_in_use": ("gauge", ()),
    "dstack_tpu_serving_kv_cow_copies_total": ("counter", ()),
    "dstack_tpu_serving_kv_layer_blocks": ("gauge", ()),
    "dstack_tpu_serving_kv_window_dead_blocks": ("gauge", ()),
    # Recurrent state beside the rows (a model with state-space layers;
    # zero otherwise): the layers that keep rows, a slot's state bytes at
    # any context and the pool's, and the state rows (slot x state layer)
    # the launched decode steps needed against those their programs moved.
    "dstack_tpu_serving_decode_state_rows_computed_total": ("counter", ()),
    "dstack_tpu_serving_decode_state_rows_total": ("counter", ()),
    "dstack_tpu_serving_kv_pool_layers": ("gauge", ()),
    "dstack_tpu_serving_state_pool_bytes": ("gauge", ()),
    "dstack_tpu_serving_state_row_bytes": ("gauge", ()),
    # The expert bank an engine holds (all the experts its router scores,
    # or a device's share of them) and the (token, expert) pairs routing
    # chose against those that fell on an expert held.
    "dstack_tpu_serving_moe_experts_held": ("gauge", ()),
    "dstack_tpu_serving_moe_experts_published": ("gauge", ()),
    "dstack_tpu_serving_moe_local_pairs_total": ("counter", ()),
    "dstack_tpu_serving_moe_pairs_total": ("counter", ()),
    # Of those pairs, the ones whose token took the router's skip choice.
    "dstack_tpu_serving_moe_skip_pairs_total": ("counter", ()),
    # Launches whose programs pass an expert layer, and those of them whose
    # expert layers took the routed path (workloads/moe.py: `plan`).
    "dstack_tpu_serving_moe_expert_launches_total": ("counter", ()),
    "dstack_tpu_serving_moe_routed_launches_total": ("counter", ()),
    # Prefill/decode disaggregation (workloads/kv_transfer.py): handoff
    # outcome counters on both sides of the seam, payload bytes moved,
    # per-handoff transfer latency, and the depth of the handoff queue
    # (prefill: finalized tasks awaiting send; decode: received payloads
    # awaiting a slot + blocks).
    "dstack_tpu_serving_kv_handoffs_received_total": ("counter", ()),
    "dstack_tpu_serving_kv_handoffs_sent_total": ("counter", ()),
    "dstack_tpu_serving_kv_handoffs_stale_rejected_total": ("counter", ()),
    # Hierarchical KV cache (PR 16, workloads/kv_host_tier.py): host-tier
    # occupancy (spilled blocks + bytes including pinned swapped-slot
    # payloads), spill/eviction churn, block swap-ins, and the swap-in
    # latency to weigh against a cold re-prefill of the same prefix.
    "dstack_tpu_serving_kv_host_blocks": ("gauge", ()),
    "dstack_tpu_serving_kv_host_bytes": ("gauge", ()),
    "dstack_tpu_serving_kv_host_evictions_total": ("counter", ()),
    "dstack_tpu_serving_kv_spills_total": ("counter", ()),
    "dstack_tpu_serving_kv_swap_in_seconds": ("histogram", ("role",)),
    "dstack_tpu_serving_kv_swap_ins_total": ("counter", ()),
    "dstack_tpu_serving_kv_transfer_bytes_total": ("counter", ()),
    "dstack_tpu_serving_kv_transfer_queue_depth": ("gauge", ()),
    "dstack_tpu_serving_kv_transfer_seconds": ("histogram", ("role",)),
    # The engine loop's own time (utils/flight_recorder.PhaseClock): host
    # wall seconds per phase (wait/admit/grow/dispatch/sync/barrier/
    # fan_out, and children as "admit/match"; "admit/shadow" and
    # "fan_out/shadow" are the parts of admit and of fan_out spent behind
    # a decode launch), whole cycles, and the cycles of a second or more.
    "dstack_tpu_serving_loop_cycles_total": ("counter", ()),
    "dstack_tpu_serving_loop_phase_seconds_total": ("counter", ("phase",)),
    "dstack_tpu_serving_loop_slow_cycles_total": ("counter", ()),
    "dstack_tpu_serving_pending_requests": ("gauge", ()),
    # Per-request phase breakdown (PR 15 flight recorder): telescoping
    # phase durations — queue_wait/prefill/kv_ship/kv_adopt/decode/... —
    # labeled by the engine role they were observed on.
    "dstack_tpu_serving_phase_seconds": ("histogram", ("phase", "role")),
    "dstack_tpu_serving_prefill_chunks_total": ("counter", ()),
    "dstack_tpu_serving_prefill_tokens_total": ("counter", ()),
    # Tiered prefix-cache hit split: device hits served straight from the
    # pool, host hits resurrected from the spill tier (each also counts a
    # kv_swap_in). hits_total stays as the sum for dashboard continuity.
    "dstack_tpu_serving_prefix_cache_device_hits_total": ("counter", ()),
    "dstack_tpu_serving_prefix_cache_hits_total": ("counter", ()),
    "dstack_tpu_serving_prefix_cache_host_hits_total": ("counter", ()),
    "dstack_tpu_serving_prefix_cache_misses_total": ("counter", ()),
    "dstack_tpu_serving_prefix_tokens_reused_total": ("counter", ()),
    "dstack_tpu_serving_rejected_total": ("counter", ()),
    # Slot preemption under overcommit: currently-swapped-out slots, how
    # many preemptions have fired, and how many slots were readmitted.
    "dstack_tpu_serving_slot_preemptions_total": ("counter", ()),
    "dstack_tpu_serving_slot_swap_ins_total": ("counter", ()),
    "dstack_tpu_serving_slots_active": ("gauge", ()),
    "dstack_tpu_serving_slots_swapped": ("gauge", ()),
    # Speculative decoding (PR 10): draft/verify wall time, token fate
    # counters, and the acceptance signals behind adaptive draft length.
    "dstack_tpu_serving_spec_accept_rate_ewma": ("gauge", ()),
    "dstack_tpu_serving_spec_draft_len_mean": ("gauge", ()),
    "dstack_tpu_serving_spec_draft_seconds_total": ("counter", ()),
    "dstack_tpu_serving_spec_fallback_rounds_total": ("counter", ()),
    "dstack_tpu_serving_spec_rounds_total": ("counter", ()),
    "dstack_tpu_serving_spec_tokens_accepted_total": ("counter", ()),
    "dstack_tpu_serving_spec_tokens_proposed_total": ("counter", ()),
    "dstack_tpu_serving_spec_tokens_rejected_total": ("counter", ()),
    "dstack_tpu_serving_spec_verify_seconds_total": ("counter", ()),
    # Per-tenant QoS (dataplane/qos.py via the native server): admission
    # and shed counts, and the per-tenant TTFT distribution the
    # noisy-neighbor bench reads. See the cardinality note on
    # dstack_tpu_serving_adapters_loaded.
    "dstack_tpu_serving_tenant_requests_total": ("counter", ("tenant",)),
    "dstack_tpu_serving_tenant_shed_total": ("counter", ("tenant",)),
    "dstack_tpu_serving_tenant_ttft_seconds": ("histogram", ("tenant",)),
    # Decode time per emitted token, one sample per decode chunk / spec
    # round (chunk wall time over tokens emitted) — the series the
    # disaggregation bench's decode-isolation check reads.
    "dstack_tpu_serving_tpt_seconds": ("histogram", ("role",)),
    # Was a lone `_sum` counter with no `_count` partner (unscrapeable as
    # a summary); now a first-class histogram. The role label separates a
    # split request's prefill leg (submit -> handoff acked), decode leg
    # (receipt -> first delivery) and a unified engine's full TTFT —
    # different quantities that must not aggregate into one distribution.
    "dstack_tpu_serving_ttft_seconds": ("histogram", ("role",)),
    # Warmup pass wall time (engine.warmup(): pre-building every jitted
    # program before /readyz flips ready). One sample per boot; the
    # cold/warm-cache gap IS the persistent cache's win. The cold_start
    # role value on the TTFT histogram above tags first tokens delivered
    # by a warmup-less boot's first-ever request.
    "dstack_tpu_serving_warmup_seconds": ("histogram", ()),
    # Spec cache (PR 3).
    "dstack_tpu_spec_cache_entries": ("gauge", ()),
    "dstack_tpu_spec_cache_hit_rate": ("gauge", ()),
    "dstack_tpu_spec_cache_hits_total": ("counter", ("model",)),
    "dstack_tpu_spec_cache_misses_total": ("counter", ("model",)),
    # Span latency aggregates.
    "dstack_tpu_span_count_total": ("counter", ("span",)),
    "dstack_tpu_span_seconds_sum": ("counter", ("span",)),
}


_HISTOGRAM_SUFFIXES = ("_bucket", "_sum", "_count")


def counter_name(tracer_counter: str) -> str:
    """Prometheus name a `tracer.inc(name, ...)` counter is exposed as."""
    return f"{PREFIX}{tracer_counter}_total"


def histogram_name(tracer_histogram: str) -> str:
    """Prometheus base name a `tracer.observe(name, ...)` histogram is
    exposed under (`_bucket`/`_sum`/`_count` are derived from it)."""
    return f"{PREFIX}{tracer_histogram}"


def histogram_base(name: str) -> Optional[str]:
    """Base declaration behind a derived histogram series name, or None
    if `name` is not `<declared histogram>_bucket/_sum/_count`."""
    for suffix in _HISTOGRAM_SUFFIXES:
        if name.endswith(suffix):
            base = name[: -len(suffix)]
            if METRICS.get(base, ("",))[0] == "histogram":
                return base
    return None


def metric_type(name: str) -> str:
    """Declared exposition type; raises KeyError for undeclared names so
    emission-time drift fails loudly in tests. Derived histogram series
    resolve through their base declaration."""
    decl = METRICS.get(name)
    if decl is not None:
        return decl[0]
    if histogram_base(name) is not None:
        return "histogram"
    raise KeyError(name)
