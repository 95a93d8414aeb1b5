"""Continuous-batching decode engine (JetStream-style), TPU-native.

`generate.py` decodes one request at a time; this module keeps a fixed
batch of B *slots* stepping together so new requests join mid-flight and
finished ones free their slot immediately — the standard way to keep the
MXU busy while serving many streams.

The KV cache is PAGED (workloads/kv_blocks.py): slots index a shared
block pool through per-slot block tables instead of owning dense
`max_len` strips, so short requests hold only the blocks they filled and
requests sharing a prompt prefix share its blocks refcounted
(copy-on-write on divergence). Prompt admission is CHUNKED: each loop
iteration dispatches at most `prefill_chunk_tokens` prompt tokens —
split across up to `max_prefills_per_chunk` requests — before the
decode chunk, so a long prompt never stalls in-flight decodes for more
than one chunk budget and TTFT under burst stops scaling with
prompt_len × streams.

Three kinds of jitted program run the engine:

- chunk_prefill (one per pow-2 chunk bucket): one prompt chunk straight
  into the slot's pool blocks; the final chunk samples the first token
  on device AND flips the slot live — admission needs no insert program
  and no host round-trip;
- paged decode_step: `steps_per_sync` tokens for ALL active slots per
  host sync, attending raggedly over the block tables
  (paged_attention.ragged_attention — no dense view is ever gathered)
  and writing only each step's new row;
- copy_block: the device half of copy-on-write.

First tokens are delivered by a dedicated reader thread the moment the
prefill readback lands — because prefill chunks are dispatched BEFORE
the decode chunk each iteration, that readback completes while the
decode chunk still runs, so TTFT does not pay the decode chunk's
residual (up to `steps_per_sync` steps).

The reference semantics are `generate.generate`'s:
tests/test_serving_paged.py pins chunked+paged token streams to it
bit-exactly at temperature 0 (kv_blocks.py's header).
"""

import queue
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.server.tracing import HistogramData
from dstack_tpu.utils.flight_recorder import (
    LOOP_CHILDREN,
    LOOP_PHASES,
    FlightRecorder,
    PhaseClock,
)
from dstack_tpu.utils.stagemarkers import auto_stage
from dstack_tpu.workloads import compile_cache
from dstack_tpu.workloads.config import MAMBA, ModelConfig
from dstack_tpu.workloads.kv_blocks import (
    BlockAllocator,
    init_paged_state,
    make_chunk_prefill,
    make_copy_block,
    make_paged_decode_step,
    make_spec_draft,
    make_spec_verify,
)
from dstack_tpu.workloads.kv_host_tier import HostKVTier
from dstack_tpu.workloads.kv_transfer import KVHandoff, StaleEpochError
from dstack_tpu.workloads.paged_attention import (
    dispatch_path as attn_dispatch_path,
)
from dstack_tpu.workloads import moe
from dstack_tpu.workloads.quant import QTensor, quantize_params
from dstack_tpu.workloads.selective_scan import scan_impl
from dstack_tpu.workloads.sharding import (
    make_serving_shardings,
    serving_param_shardings,
)

Params = Dict[str, Any]


class EngineOverloadedError(RuntimeError):
    """submit() rejected because the pending queue is at max_pending.

    `retry_after` is the engine's own estimate (seconds) of when a slot
    is likely to free up — callers surface it as an HTTP Retry-After.
    Shedding at admission keeps TTFT bounded for accepted requests;
    unbounded queueing trades a TTFT that grows with the backlog for a
    few percent of aggregate throughput.
    """

    def __init__(self, pending: int, retry_after: float):
        super().__init__(
            f"serving engine overloaded: {pending} requests already queued"
        )
        self.pending = pending
        self.retry_after = retry_after


class EngineBusyError(RuntimeError):
    """warmup() refused because the engine is not idle: a request was
    admitted first. Its own type so that a caller can tell this benign
    refusal from a program that failed to build — compiler and runtime
    errors are RuntimeErrors too."""


# stats() key of each loop phase ("admit") and child ("admit/match").
_LOOP_SECONDS_KEYS = {
    key: f"loop_{key.replace('/', '_')}_seconds_total"
    for key in LOOP_PHASES + LOOP_CHILDREN
}


def _span_id(req: "_Request") -> Any:
    """What names a request on an `engine/*` span: the flight recorder's
    id of it (the key of GET /v1/requests/<id>/trace)."""
    rid = req.trace.request_id if req.trace is not None else req.request_id
    return "" if rid is None else rid


class _Request(NamedTuple):
    tokens: List[int]
    max_new_tokens: int
    # Yields int tokens; None = clean end; an Exception = engine failure
    # (consumers must re-raise, not treat partial output as complete).
    out: "queue.Queue[object]"
    temperature: float  # per-request; 0 = greedy
    top_p: float        # per-request nucleus cutoff; 1 = no filtering
    t_submit: float     # monotonic submit time (TTFT / queue-wait gauges)
    # Caller-supplied correlation id, carried on the KV handoff so a
    # disaggregated front-end can match decode-side streams back to the
    # prompts it submitted to the prefill worker. None = engine-assigned.
    request_id: Optional[int] = None
    # Multi-tenant LoRA: the adapter this request selected (None = base
    # model) and its device pool slot at submit time. The name doubles as
    # the prefix-cache namespace so tenants never share poisoned blocks.
    adapter: Optional[str] = None
    adapter_ix: int = -1
    # Per-request observability: the W3C traceparent this request rides
    # (propagated onto the KV handoff) and its flight-recorder timeline
    # (None when the recorder is off). Appended with defaults — callers
    # construct _Request positionally.
    traceparent: Optional[str] = None
    trace: Optional[Any] = None
    # QoS identity: keys the engine's qos_weights map (same weights the
    # dataplane DRR scheduler uses), deciding who preempts whom when the
    # host tier lets admitted streams overcommit residency. None = the
    # default weight (1.0).
    tenant: Optional[str] = None


class _SwappedSlot:
    """A preempted request parked in host memory: the gathered KV of its
    whole block chain (target + drafter pools) plus the device sampling
    scalars at the chunk boundary — everything readmission needs to
    resume decode bit-exactly at temperature 0. The request's adapter
    ref is NOT released across the swap (the registry hold must outlive
    the preemption or the adapter could be evicted under it); `nbytes`
    is pinned in the HostKVTier budget until readmission or a terminal
    path unreserves it."""

    __slots__ = ("req", "length", "last_token", "remaining", "arrays",
                 "nbytes", "t_swap", "t0")

    def __init__(self, req: _Request, length: int, last_token: int,
                 remaining: int, arrays: Dict[str, np.ndarray],
                 nbytes: int, t_swap: float, t0: float):
        self.req = req
        self.length = length          # filled cache positions at swap
        self.last_token = last_token  # next token to feed
        self.remaining = remaining    # decode budget left
        self.arrays = arrays          # k/v (+draft_k/draft_v), (L,n,bs,KV,hd)
        self.nbytes = nbytes          # reserved against the host budget
        self.t_swap = t_swap
        self.t0 = t0                  # original slot admission time


class _PrefillTask:
    """A request mid-chunked-prefill: owns a slot and a growing block
    table from admission until its final chunk dispatches. `first` is a
    device scalar future set at finalize; `delivered` flips once the
    reader thread has pushed the first token to the consumer (the loop
    waits on it before fanning out decode tokens that could otherwise
    overtake it)."""

    __slots__ = ("req", "slot", "pos", "table", "first", "t_pop",
                 "delivered", "finalized", "kv_payload")

    def __init__(self, req: _Request, slot: int, pos: int, table: List[int],
                 t_pop: float):
        self.req = req
        self.slot = slot
        self.pos = pos          # prompt tokens already in cache (prefix hits)
        self.table = table      # host copy of the slot's block table
        self.first: Optional[jnp.ndarray] = None
        self.t_pop = t_pop
        self.delivered = threading.Event()
        self.finalized = False
        # Prefill role only: device gathers of the finished blocks (and
        # drafter blocks), dispatched at finalize on the loop thread —
        # the sender thread reads these back, never self.state (whose
        # buffers later chunk dispatches donate).
        self.kv_payload: Optional[Dict[str, Any]] = None


class ServingEngine:
    """Continuous-batching host loop around the jitted programs.

    submit() returns a queue yielding generated token ids as they decode
    (None terminates) — callers stream them straight out (SSE) or collect.
    """

    def __init__(
        self,
        config: ModelConfig,
        params: Params,
        *,
        slots: int = 8,
        max_len: Optional[int] = None,
        temperature: float = 0.0,
        seed: int = 0,
        steps_per_sync: int = 4,
        max_pending: Optional[int] = None,
        max_prefills_per_chunk: int = 4,
        prefill_chunk_tokens: int = 128,
        kv_block_size: int = 16,
        kv_pool_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        spec_enable: bool = False,
        spec_max_draft: int = 4,
        spec_draft_params: Optional[Params] = None,
        spec_draft_config: Optional[ModelConfig] = None,
        spec_min_accept: float = 0.3,
        kv_budget_bytes: Optional[int] = None,
        mesh: Optional[Any] = None,
        role: str = "unified",
        kv_transfer: Optional[Any] = None,
        lora_max_adapters: int = 0,
        lora_rank: int = 8,
        lora_targets: Optional[Tuple[str, ...]] = None,
        trace_ring: int = 256,
        trace_slow_ms: Optional[float] = None,
        kv_host_budget_bytes: Optional[int] = None,
        max_resident_slots: Optional[int] = None,
        qos_weights: Optional[Dict[str, float]] = None,
    ):
        # Persistent compile cache, placed by compile_cache.enable()'s
        # precedence, live before any jitted program below is built: a
        # repeat boot of the same model retrieves its whole program set
        # from disk instead of recompiling. The monitoring counters it
        # installs back warmup()'s zero-post-ready-compile contract.
        self._compile_cache_dir = compile_cache.enable()
        self.config = config
        self.params = params
        self.slots = slots
        self.max_len = max_len or config.max_seq_len
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be unified/prefill/decode, got {role!r}"
            )
        self.role = role
        # Per-request flight recorder (PR 15): bounded ring of phase
        # timelines, trace_ring=0 disables it entirely. Tail capture
        # (full snapshots of slow/error/shed requests) is opt-in via
        # trace_slow_ms.
        self.recorder = FlightRecorder(
            capacity=trace_ring, slow_ms=trace_slow_ms, role=role
        )
        if max_prefills_per_chunk < 1:
            raise ValueError(
                f"max_prefills_per_chunk must be >= 1, got {max_prefills_per_chunk}"
            )
        if prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got {prefill_chunk_tokens}"
            )
        if kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {kv_block_size}"
            )
        if self.max_len % kv_block_size != 0:
            raise ValueError(
                f"kv_block_size {kv_block_size} must divide"
                f" max_len {self.max_len}"
            )
        self._block_size = kv_block_size
        self._max_blocks = self.max_len // kv_block_size
        # Default pool = dense-equivalent (every slot can grow to
        # max_len even with zero sharing, so allocation cannot fail at
        # the defaults; prefix sharing then turns the saved blocks into
        # cache headroom). Smaller pools trade worst-case capacity for
        # HBM — submit() bounds each request to fit, but concurrent
        # worst-case slots can still exhaust a small pool mid-decode,
        # which force-retires the starved slot with an error.
        self._num_blocks = (
            kv_pool_blocks if kv_pool_blocks is not None
            else slots * self._max_blocks
        )
        if self._num_blocks < self._max_blocks:
            raise ValueError(
                f"kv_pool_blocks {self._num_blocks} must fit one max_len"
                f" request ({self._max_blocks} blocks)"
            )
        # -- hierarchical KV: host-memory tier + slot preemption ----------
        # With a host budget, LRU-evicted prefix-cache blocks spill to
        # host RAM instead of dying (a later prefix hit swaps them back
        # in — cheaper than re-prefill), and whole slots can swap out
        # under pressure or QoS preemption. Off (None/0) the engine is
        # byte-for-byte the pre-tier engine.
        self._host_tier: Optional[HostKVTier] = None
        if kv_host_budget_bytes:
            self._host_tier = HostKVTier(kv_host_budget_bytes)
        if max_resident_slots is None:
            self._max_resident = slots
        else:
            if not (1 <= max_resident_slots <= slots):
                raise ValueError(
                    f"max_resident_slots {max_resident_slots} must be in"
                    f" [1, slots={slots}]"
                )
            if max_resident_slots < slots and self._host_tier is None:
                raise ValueError(
                    "max_resident_slots < slots requires a host tier to"
                    " park swapped slots in (set kv_host_budget_bytes)"
                )
            self._max_resident = max_resident_slots
        self._qos_weights: Dict[str, float] = dict(qos_weights or {})
        # Preempted requests parked in the host tier, readmitted
        # highest-weight-first at admission boundaries. Guarded by _lock.
        self._swapped: List[_SwappedSlot] = []
        # One-slot peek buffer for the pending queue's head: a request
        # popped for admission that found no free slot (and could not
        # queue-jump) waits here instead of being re-queued behind
        # later arrivals. Loop thread only, but counted by submit()'s
        # backlog accounting under _lock.
        self._next_req: Optional[_Request] = None
        # Out-queues whose live slot should be preempted at the next
        # boundary (the preempt() API; guarded by _lock).
        self._preempt_requests: set = set()
        self._preemptions = 0       # slots swapped out, monotonic
        self._slot_swap_ins = 0     # slots swapped back in, monotonic
        self._swap_in_hist = HistogramData()
        # A model with state-space layers runs with prefix reuse OFF: a
        # matched block chain is worth nothing without the recurrent state
        # at its end, which nothing keeps (ROADMAP R5: a snapshot entry).
        # Read here, once; `match` then hands no request a chain, and
        # stats()["prefix_cache"] names the reason.
        self._prefix_cache = (
            "off: state-space layers (a cached chain lacks the recurrent"
            " state at its end)" if config.has_state_layers
            else "on" if prefix_cache else "off: prefix_cache=False"
        )
        self._alloc = BlockAllocator(
            self._num_blocks, kv_block_size,
            cache=self._prefix_cache == "on",
            spill=(self._spill_block if self._host_tier is not None
                   else None),
            swap_in=(self._swap_in_block if self._host_tier is not None
                     else None),
        )
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self._chunk_cache: Dict[int, Any] = {}
        # -- tensor-parallel serving (mesh != None) -----------------------
        # Column-parallel layout ("model" only on output dims; see
        # sharding.SERVING_PARAM_SPECS): params and KV pools are
        # device_put with explicit NamedShardings and every jitted
        # program below is built with matching in/out shardings — the
        # SAME traced programs serve partitioned state, and because no
        # contraction axis is ever split, sharded temp-0 output stays
        # bit-exact vs a single-device engine.
        self.mesh = mesh
        self._model_shards = 1
        self._shardings = None
        if mesh is not None:
            if "model" not in getattr(mesh, "shape", {}):
                raise ValueError("serving mesh must carry a 'model' axis")
            ms = int(mesh.shape["model"])
            self._model_shards = ms
            for what, mc in (
                ("target", config),
                ("drafter", spec_draft_config or config),
            )[: 2 if spec_enable else 1]:
                if mc.n_heads % ms or mc.n_kv_heads % ms:
                    raise ValueError(
                        f"{what} heads ({mc.n_heads} q / {mc.n_kv_heads} kv)"
                        f" must divide the mesh's model axis ({ms})"
                    )
        # Engine features that assume GQA rows or wq/wk/wv weights, one
        # stack of layers, or layers of one kind, and have no test with
        # anything else: a latent-attention, dense-leading or mixed-layer
        # (layer_types) model names the feature and stops here; none may
        # run and give other numbers. A model with state-space layers is a
        # mixed-layer model, and has reasons of its own: a rejected draft
        # would have to roll the state back, a preempted or handed-over
        # block chain drops it, and its mixers are stacks of their own.
        if config.latent or config.n_dense_layers or config.layer_types:
            quantized = any(
                isinstance(leaf, QTensor) for leaf in jax.tree_util.tree_leaves(
                    params, is_leaf=lambda x: isinstance(x, QTensor))
            )
            for feature, asked in (
                ("LoRA adapter banks (lora_max_adapters)", lora_max_adapters > 0),
                ("int8 weights (--quantize int8)", quantized),
                ("a model-sharded mesh (--mesh-model > 1)", mesh is not None),
                ("speculative decoding (--spec-enable)", bool(spec_enable)),
                ("the prefill/decode split (--role)", role != "unified"),
                ("the host KV tier (--kv-host-budget-mb)",
                 bool(kv_host_budget_bytes)),
            ):
                if asked:
                    raise ValueError(
                        f"{feature} is not supported for latent-attention,"
                        " dense-leading or mixed-layer (layer_types, or"
                        " state-space layers by attn_layer_period) models:"
                        " it assumes per-head K/V rows, wq/wk/wv weights, one"
                        " stack of layers or layers of one kind, and no"
                        " recurrent state beside the rows"
                    )
        self.state = init_paged_state(
            config, slots, self.max_len, kv_block_size, self._num_blocks
        )
        if mesh is not None:
            self.params = jax.device_put(
                params, serving_param_shardings(mesh, params)
            )
            self._shardings = make_serving_shardings(
                mesh, self.params, self.state
            )
            self.state = jax.device_put(self.state, self._shardings.state)
        else:
            self.state = self._commit(self.state)
        # -- multi-tenant LoRA (lora_max_adapters > 0) --------------------
        # A refcounted host registry over a device-side adapter pool; the
        # jitted programs below are built with lora=True so every batched
        # step gathers each slot's A/B pair by state.adapter_ix and
        # applies the delta unmerged (lora_serving.project_qkv_lora).
        # Disabled engines trace programs identical to pre-multitenant
        # ones — the base path pays nothing.
        self._lora: Optional[Any] = None
        if lora_max_adapters > 0:
            if role != "unified":
                raise ValueError(
                    "adapter multiplexing requires role='unified' (KV"
                    " handoffs do not carry adapter identity yet)"
                )
            from dstack_tpu.workloads.lora_serving import AdapterRegistry

            self._lora = AdapterRegistry(
                config, self.params,
                max_adapters=lora_max_adapters, rank=lora_rank,
                targets=lora_targets or ("wq", "wv"), mesh=mesh,
            )
        # out-queue -> adapter name for every in-flight adapter request;
        # _release_adapter pops exactly once per request (guarded by
        # _lock like all scheduler state).
        self._adapter_holds: Dict[Any, str] = {}
        # Which ragged-attention implementation this engine runs: one
        # static decision (shape + backend + shard count) that every
        # jitted program below is built with AND that labels
        # dstack_tpu_serving_attn_dispatch_total{path=...} — the reported
        # path is the traced path, sharded or not.
        self._attn_path = self._resolve_attn_path(config)
        self._attn_dispatch = {"pallas": 0, "lax_ragged": 0}
        # Expert slots and launches, window-diffable, counted from the
        # shapes at each launch (_count_expert_slots).
        self._moe_computed_slots = 0
        self._moe_routed_launches = 0
        self._moe_expert_launches = 0
        # The (token, expert) pairs routing chose, and those that fell on
        # an expert held here: one number, counted at the launch, where the
        # bank is whole; where it is a share of the experts routed over,
        # what the programs counted on the device (kv_blocks: `moe_pairs`),
        # read with each decode chunk's tokens.
        self._moe_pairs = 0
        self._moe_local_pairs = 0
        self._step = make_paged_decode_step(
            config, steps=steps_per_sync, shardings=self._shardings,
            lora=self._lora is not None, attn_impl=self._attn_path,
        )
        # Plain twin for LoRA engines: while no request holds an adapter
        # ref the loop dispatches this instead — the LoRA program's
        # per-layer lax.cond skips the adapter math at runtime but still
        # breaks XLA fusion across the projection, a real per-step cost
        # the adapter-free path shouldn't pay.
        self._step_base = self._step if self._lora is None else \
            make_paged_decode_step(
                config, steps=steps_per_sync, shardings=self._shardings,
                attn_impl=self._attn_path,
            )
        self._copy_block = make_copy_block(shardings=self._shardings)
        # -- speculative decoding (drafter proposes k, target verifies
        # k+1 in one forward; see kv_blocks.make_spec_draft/_verify).
        self._spec = bool(spec_enable)
        if spec_max_draft < 1:
            raise ValueError(
                f"spec_max_draft must be >= 1, got {spec_max_draft}"
            )
        self._spec_max_draft = spec_max_draft
        self._spec_min_accept = spec_min_accept

        def _pool_bytes(cfg: ModelConfig) -> int:
            return (cfg.n_attn_layers * self._num_blocks * kv_block_size
                    * cfg.kv_row_bytes())

        self._draft_config = spec_draft_config or config
        # The drafter's programs attend over its own pool geometry.
        self._draft_attn_path = self._resolve_attn_path(self._draft_config)
        # Exposed so deployment surfaces (and tests) can size
        # kv_budget_bytes against the actual pool footprint.
        self._pool_bytes_target = _pool_bytes(config)
        if self._spec:
            if self._draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    "drafter vocab_size"
                    f" {self._draft_config.vocab_size} must match the"
                    f" target's {config.vocab_size} (one tokenizer)"
                )
            # The drafter must cover as much of the engine window as
            # the target does (the target may itself run a max_len
            # beyond its preset's max_seq_len — RoPE extrapolation —
            # and then the drafter only has to match that coverage).
            target_cover = min(self.max_len, config.max_seq_len)
            if self._draft_config.max_seq_len < target_cover:
                raise ValueError(
                    f"drafter max_seq_len {self._draft_config.max_seq_len}"
                    f" must cover the engine window {target_cover}"
                    f" (min of engine max_len {self.max_len} and target"
                    f" max_seq_len {config.max_seq_len})"
                )
        if kv_budget_bytes is not None:
            need_bytes = _pool_bytes(config)
            if self._spec:
                need_bytes += _pool_bytes(self._draft_config)
            if need_bytes > kv_budget_bytes:
                what = ("a drafter KV pool alongside the target pool"
                        if self._spec else "the KV pool")
                raise ValueError(
                    f"cannot fit {what}: {need_bytes} bytes needed but"
                    f" kv_budget_bytes is {kv_budget_bytes}"
                    + (" (disable speculation or shrink the pool)"
                       if self._spec else "")
                )
        if self._spec:
            # Default drafter: weight-only int8 of the target — same
            # tree shape (QTensor leaves dispatch in transformer.linear)
            # so every jitted program runs unchanged.
            self._draft_params = (
                spec_draft_params if spec_draft_params is not None
                else quantize_params(params)
            )
            # The drafter pool mirrors the target pool's GEOMETRY
            # (num_blocks x block_size) and is indexed through the SAME
            # block tables: one allocator drives both, so prefix
            # sharing, CoW and eviction decisions stay coherent across
            # the two models. Its own table/scalar fields are unused.
            self._draft_state = init_paged_state(
                self._draft_config, slots, self.max_len, kv_block_size,
                self._num_blocks,
            )
            self._draft_shardings = None
            if mesh is not None:
                # QTensor leaves: q mirrors the float parent's column-
                # parallel spec, per-channel scales replicate (see
                # sharding._broadcast_specs).
                self._draft_params = jax.device_put(
                    self._draft_params,
                    serving_param_shardings(mesh, self._draft_params),
                )
                self._draft_shardings = make_serving_shardings(
                    mesh, self._draft_params, self._draft_state
                )
                self._draft_state = jax.device_put(
                    self._draft_state, self._draft_shardings.state
                )
            else:
                self._draft_state = self._commit(self._draft_state)
            self._copy_draft_block = make_copy_block(
                shardings=self._draft_shardings
            )
            self._draft_chunk_cache: Dict[int, Any] = {}
            self._spec_draft_fns: Dict[int, Any] = {}
            self._spec_verify_fns: Dict[int, Any] = {}
        # Per-slot adaptive draft length: starts mid, grows toward
        # spec_max_draft while the slot's acceptance EWMA stays high,
        # shrinks toward 1 when it drops. None EWMA = unseeded.
        self._spec_init_k = min(2, spec_max_draft)
        self._slot_k: List[int] = [self._spec_init_k] * slots
        self._accept_ewma: List[Optional[float]] = [None] * slots
        self._spec_accept_ewma = 0.0      # batch mean (stats gauge)
        self._spec_tokens_round_ewma = 0.0  # emitted tokens per round
        self._spec_rounds = 0
        self._spec_fallback_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rejected = 0
        self._t_spec_draft = 0.0
        self._t_spec_verify = 0.0
        # Whole-batch fallback: after `_spec_low_streak` consecutive
        # rounds with batch-mean acceptance below spec_min_accept, run
        # plain decode chunks for `_SPEC_COOLDOWN` boundaries, then
        # re-probe at k=1 — bounding the adversarial-drafter loss to
        # the probe rounds' overhead.
        self._spec_low_streak = 0
        self._spec_cooldown = 0
        # Per-row table push with fixed shapes ((slots, max_blocks) +
        # scalar + (max_blocks,)): one compile ever, hit during warmup.
        # A batched .at[slots].set(rows) would recompile per
        # number-of-rows-grown — a ~0.5 s XLA stall the first time a
        # multi-stream scenario grows several tables in one boundary.
        self._set_table_row = jax.jit(
            lambda bt, slot, row: bt.at[slot].set(row), donate_argnums=0
        )
        self._temperature = temperature
        self._rng = jax.random.PRNGKey(seed)
        # Separate drafter stream: at temperature 0 both paths are
        # greedy (rng unused), so keeping the target's stream untouched
        # is what makes spec-on output bit-identical to spec-off.
        self._rng_draft = jax.random.PRNGKey(seed + 0x5bec)
        # Admission control: None = unbounded (library embedding decides);
        # servers should bound it — see EngineOverloadedError.
        self.max_pending = max_pending
        self.rejected = 0  # total sheds, monotonic (for /metrics)
        self._steps_per_sync = steps_per_sync
        self.max_prefills_per_chunk = max_prefills_per_chunk
        self._chunk_s = 0.05  # EWMA wall time per decode chunk (seeded)
        self._turn_s = 1.0    # EWMA slot occupancy admit->retire (seeded)
        # Scheduler gauges (seeded on first sample): TTFT submit->first
        # token, queue wait submit->admission, prefill admission->first
        # token — the autoscaler/gateway read these from stats().
        self._ttft_s = 0.0
        self._queue_wait_s = 0.0
        self._prefill_s = 0.0
        # Monotonic sum/count behind the EWMAs (Prometheus summary
        # style): scrapers and the bench diff these per window for exact
        # per-window means, immune to EWMA warm-up/compile spikes.
        self._n_admitted = 0
        self._sum_ttft = 0.0
        self._sum_queue_wait = 0.0
        self._sum_prefill = 0.0
        # Log-bucket TTFT histogram behind the sum/count pair: /metrics
        # exposes dstack_tpu_serving_ttft_seconds as a real histogram so
        # scrapers get quantiles, not just per-window means.
        self._ttft_hist = HistogramData()
        # Cold-start TTFT split: until warmup() has run OR a first token
        # has been delivered, TTFT samples land under role="cold_start" —
        # the sample that paid compilation on a warmup-less boot. A
        # warmup-gated boot keeps this bucket empty, which is the point.
        self._ttft_cold_hist = HistogramData()
        self._cold_over = False
        # warmup() bookkeeping: whether the full jitted program set has
        # been pre-built, how long that took, and how many programs it
        # covered (stats()/prometheus surface all three).
        self._warmup_done = False
        self._warmup_seconds: Optional[float] = None
        self._warmup_programs = 0
        self._warmup_hist = HistogramData()
        # One first_token timeline marker per engine lifetime (stage
        # markers ride stdout; see utils/stagemarkers.py).
        self._first_token_emitted = False
        # The loop's own time: every phase of every cycle goes through
        # this one clock, which feeds the loop_* counters of stats() and,
        # while a profiler runs, `engine/<phase>` spans on its timeline.
        self._clock = PhaseClock(annotate=jax.profiler.TraceAnnotation)
        # Barrier seconds of cycles with nothing live: admission's, in
        # the prefill_seconds_total derivation (stats()).
        self._admission_barrier_s = 0.0
        # Work at the scheduler's boundary: decode steps launched, the
        # same weighted by live slots, and the tokens they emitted.
        self._decode_steps = 0
        self._decode_slot_steps = 0
        self._decode_tokens = 0
        # What those steps gave the paged-attention kernel to walk: the
        # blocks the live slots' contexts fill, against the columns of
        # the whole block table (slots x max blocks).
        self._decode_live_blocks = 0
        self._decode_table_columns = 0
        # Layer by layer: the blocks the live slots' tables hold, those
        # the attention walks visit, and those of window layers that lie
        # wholly behind their slot's window (_layer_blocks).
        self._decode_layer_blocks = 0
        self._decode_attended_blocks = 0
        self._decode_window_dead_blocks = 0
        # State rows (slot x state-space layer) the update of each launched
        # step needs, the live slots', against those the program moves:
        # the same on the TPU, whose kernel walks live rows only
        # (selective_scan.selective_scan_decode); every slot row's, live or
        # not, in the plain form.
        self._scan_path = (
            scan_impl(*config.state_shapes()[0])
            if config.has_state_layers else "none"
        )
        self._decode_state_rows = 0
        self._decode_state_rows_computed = 0
        # Chunked-prefill / paging counters (monotonic, for /metrics and
        # the prefix-reuse acceptance measurement: tokens_computed for a
        # cache-hit request drops by the reused prefix).
        self._prefill_chunks = 0
        self._prefill_tokens_computed = 0
        self._slot_t0: List[float] = [0.0] * slots
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._live: List[Optional[_Request]] = [None] * slots
        # Host mirrors of per-slot cache length and block table for
        # decode-growth allocation and retire-time release (loop thread
        # only; table lists are also read by stats() counters via the
        # allocator, under _lock).
        self._lengths_host: List[int] = [0] * slots
        self._slot_tables: List[Optional[List[int]]] = [None] * slots
        # Requests popped for prefill but not yet live (the chunked
        # admission window): admission accounting must see them as
        # occupying capacity, and _flush_all must terminate their
        # consumers too. Guarded by _lock.
        self._admitting: List[_Request] = []
        self._tasks: List[_PrefillTask] = []
        # Finalized tasks whose first token the reader thread has not
        # confirmed delivered yet — the loop waits on these after each
        # decode sync so decode tokens never overtake the first token.
        self._pending_activation: List[_PrefillTask] = []
        # The decode chunk in flight, one record from its launch to its
        # settlement (`_Chunk`, below the engine; None while host and device
        # agree), and the last settled chunk until its tokens are handed
        # out, which happens behind the NEXT launch: two records at once.
        self._chunk: Optional["_Chunk"] = None
        self._settled: Optional["_Chunk"] = None
        # Keys split ahead, in a launch's shadow, for the next launch: the
        # chains `_rng` / `_rng_draft` are consumed in the order they were.
        self._key_ahead, self._draft_key_ahead = None, None
        self._shadow_spent = 0  # prompt tokens the last shadow launched
        self._deliver_q: "queue.Queue[Optional[_PrefillTask]]" = queue.Queue()
        # Output queues whose consumer is gone (client disconnect, stop
        # sequence hit): the loop retires their slots at the next chunk
        # boundary instead of decoding the rest of the budget into a
        # queue nobody reads. _inflight tracks queues with an unfinished
        # request so cancel() of an already-completed stream is a no-op
        # (NOT a set leak — consumers routinely cancel in a finally).
        # Both guarded by _lock.
        self._cancelled: set = set()
        self._inflight: set = set()
        self._wake = threading.Event()
        self._hold_admission = False
        self._stop = False
        self._failed: Optional[BaseException] = None
        # Guards the submit-vs-close/failure window: a request must never
        # land on _pending after _flush_all drained it (its consumer would
        # block forever).
        self._lock = threading.Lock()
        # -- prefill/decode disaggregation (role != "unified") -------------
        # A prefill engine never activates decode slots: finalized tasks
        # divert to _handoff_q, where a sender thread ships the gathered
        # KV blocks + metadata through `kv_transfer` (a
        # kv_transfer.TransferClient or anything with .send(KVHandoff)).
        # A decode engine accepts handoffs via submit_prefilled(): queued
        # under _prefilled_pending, admitted by the loop thread into
        # fresh blocks from ITS allocator. Epoch fencing: the decode
        # side's handoff_epoch must match every payload's stamp, so a
        # pool-generation change (bump_handoff_epoch) rejects in-flight
        # KV instead of absorbing bytes computed against dead state.
        self._kv_transfer = kv_transfer
        if role == "prefill" and kv_transfer is None:
            raise ValueError(
                "role='prefill' requires a kv_transfer client to ship"
                " finished prefills to (see workloads/kv_transfer.py)"
            )
        self.handoff_epoch = 1
        self._handoff_seq = 0
        self._handoff_q: "queue.Queue[Optional[_PrefillTask]]" = queue.Queue()
        # (handoff, out queue, receipt time) triples awaiting a slot +
        # blocks on the decode side; guarded by _lock.
        self._prefilled_pending: List[Tuple[KVHandoff, Any, float]] = []
        self._handoffs_sent = 0
        self._handoffs_received = 0
        self._handoff_stale_rejected = 0
        self._kv_transfer_bytes = 0
        self._kv_transfer_hist = HistogramData()
        # Decode time per emitted token, sampled once per chunk/spec
        # round (the chunk's wall time from launch to read-back over the
        # tokens it emitted) — the TPT series behind the disaggregation
        # bench's decode-isolation check.
        self._tpt_hist = HistogramData()
        self._gather_fns: Dict[int, Any] = {}
        self._inject_fns: Dict[Tuple[int, bool], Any] = {}
        self._place_slot_fn: Optional[Any] = None
        self._deliver_thread = threading.Thread(
            target=self._deliver_loop, daemon=True
        )
        self._deliver_thread.start()
        self._handoff_thread: Optional[threading.Thread] = None
        if role == "prefill":
            self._handoff_thread = threading.Thread(
                target=self._handoff_loop, daemon=True
            )
            self._handoff_thread.start()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def hold_admission(self) -> None:
        """Gate new-request admission (in-flight work continues).

        A gang-synchronous caller (the RL actor, workloads/rl.py) wraps
        each rollout round's submits in hold/release so the whole round
        enters prefill as ONE admission wave. Without the gate the loop
        thread races the submitting thread: a round may split across
        admission waves, which changes how many prefill/decode chunks —
        and therefore how many sampler rng splits — the round consumes,
        the difference between a bit-reproducible seeded rollout and
        not. submit() keeps enqueueing normally while held."""
        self._hold_admission = True

    def release_admission(self) -> None:
        self._hold_admission = False
        self._wake.set()

    def refresh_params(self, params: Params) -> int:
        """Atomically adopt a fresh parameter pytree (RL weight refresh).

        Legal only at an idle boundary: a live slot's KV (and any
        finalized prefill's first token) was computed under the old
        weights, so decoding its continuation under new ones yields a
        sequence that belongs to NEITHER policy — the RL actor's
        post-hoc behavior-logprob scorer would silently mis-score it.
        Raises RuntimeError while anything is in flight; callers drain
        first (the RL actor refreshes between rollout rounds, where the
        engine is idle by construction).

        The prefix cache is dropped on both tiers — device entries and
        host-RAM spills — because cached KV embeds the old weights and
        a post-swap prefix hit would graft stale keys/values under the
        new policy. LoRA engines refuse: the AdapterRegistry holds
        base-param references fixed at load time. Returns the number of
        cache entries dropped."""
        if self._lora is not None:
            raise RuntimeError(
                "refresh_params on a LoRA engine would orphan the"
                " adapter registry's base-param bindings; rebuild the"
                " engine instead"
            )
        new_leaves, new_tree = jax.tree_util.tree_flatten(params)
        old_leaves, old_tree = jax.tree_util.tree_flatten(self.params)
        if new_tree != old_tree or any(
            tuple(a.shape) != tuple(b.shape)
            or jnp.dtype(a.dtype) != jnp.dtype(b.dtype)
            for a, b in zip(new_leaves, old_leaves)
        ):
            raise ValueError(
                "refreshed params do not match the engine's parameter"
                " tree (structure / leaf shapes / dtypes must be equal)"
            )
        with self._lock:
            busy = (
                any(r is not None for r in self._live)
                or self._tasks or self._admitting or self._swapped
                or self._pending_activation or self._prefilled_pending
                or self._next_req is not None
                or not self._pending.empty()
            )
            if busy:
                raise RuntimeError(
                    "refresh_params requires an idle engine: drain"
                    " in-flight requests first (a mid-request swap"
                    " would decode a continuation no single policy"
                    " generated)"
                )
            if self.mesh is not None:
                params = jax.device_put(
                    params, serving_param_shardings(self.mesh, params)
                )
            self.params = params
            dropped = self._alloc.drop_cache()
            if self._host_tier is not None:
                dropped += self._host_tier.clear()
        return dropped

    def _observe_ttft(self, dt: float) -> None:
        """TTFT histogram sample, split by cold start: the first token an
        engine that never ran warmup() ever delivers paid the jit
        trace+compile for its whole dispatch chain — a different
        distribution that must not pollute the steady-state one."""
        if self._cold_over:
            self._ttft_hist.observe(dt)
        else:
            self._ttft_cold_hist.observe(dt)
            self._cold_over = True

    def _commit(self, state):
        """Place a fresh (unsharded) state on the params' device as
        COMMITTED arrays. Every program returns committed state once any
        input is committed — and checkpoint-restored params are — while
        `init_paged_state` hands out uncommitted arrays. jit keys its
        cache on that difference, so a state that starts uncommitted
        makes the first live dispatch of whatever program warmup ran
        FIRST re-trace and re-build it after /readyz."""
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        device = (
            next(iter(leaf.devices())) if isinstance(leaf, jax.Array)
            else jax.devices()[0]
        )
        return jax.device_put(state, device)

    def _resolve_attn_path(self, config: ModelConfig) -> str:
        # The kernels see the pool's row: (KV, hd), or latent attention's
        # one row for all heads.
        kv_heads, width = config.kv_row_shapes()[0]
        return attn_dispatch_path(
            self.max_len, width, self._block_size,
            dtype_bytes=jnp.dtype(config.activation_dtype).itemsize,
            num_heads=config.n_heads, num_kv_heads=kv_heads,
            model_shards=self._model_shards,
        )

    def _warmup_idle_check(self) -> None:
        """Raise unless the engine is at the idle boundary warmup needs
        (same invariant as refresh_params: warmup invokes the real
        donated-state programs, which must not race in-flight work)."""
        busy = (
            any(r is not None for r in self._live)
            or self._tasks or self._admitting or self._swapped
            or self._pending_activation or self._prefilled_pending
            or self._next_req is not None
            or not self._pending.empty()
        )
        if busy:
            raise EngineBusyError(
                "warmup requires an idle engine: call it before serving"
                " traffic (readiness gating) or after a drain"
            )

    def warmup(self) -> Dict[str, Any]:
        """Pre-build every jitted program the scheduler can dispatch, so
        the first post-ready request provably pays zero compile.

        The warmup INVOKES the real jitted callables rather than AOT-
        compiling them: `.lower().compile()` would leave jit's in-memory
        dispatch cache cold, and the first live call would still re-trace
        and (at best) re-retrieve from the persistent cache — a compile
        event the readiness contract forbids. Every invocation is a
        semantic no-op on an idle engine: a chunk prefill with n_valid=0
        and finalize=False routes all KV writes to the pad sentinel block
        and leaves every scalar field untouched (only slot 0's table row
        is set — to the all-sentinel padding admission always overwrites);
        a decode step / spec round over an all-inactive batch points its
        write lanes at the sentinel and emits nothing; block copies copy
        block 0 onto itself. Donated state is reassigned exactly like the
        live call sites do.

        Coverage: every pow-2 prefill bucket `_pad_chunk` can produce
        (plus the LoRA-indexed flavor and the drafter's twin), the decode
        step (LoRA and base), the spec draft/verify ladder for every
        draft length 1..spec_max_draft, the table-row setter, the CoW
        block copies, and the role's KV-transfer programs (pow-2 gathers
        on the prefill tier; injects + slot placement on decode).

        Emits the `compile_start`/`compile_end`/`warmup_end` stage
        markers for the run timeline, and reports the compile-counter
        delta (workloads/compile_cache.py) so callers can tell fresh
        compiles from persistent-cache retrievals. Only legal on an idle
        engine (EngineBusyError otherwise); admission stays held for the
        duration. Returns {"seconds", "programs", "compiles",
        "cache_hits", "cache_misses", "compile_seconds"}.
        """
        with self._lock:
            if self._failed is not None:
                raise RuntimeError("engine already failed") from self._failed
            self._warmup_idle_check()
            self._hold_admission = True
        t0 = time.monotonic()
        before = compile_cache.snapshot()
        auto_stage("compile_start")
        programs = 0
        try:
            # Decode step(s): all-inactive batch, write lane -> sentinel.
            self._rng, sub = jax.random.split(self._rng)
            if self._lora is not None:
                self.state, toks, _ = self._step(
                    self.params, self.state, sub, self._lora.bank
                )
                programs += 1
                self._rng, sub = jax.random.split(self._rng)
            self.state, toks, *_ = self._step_base(
                self.params, self.state, sub
            )
            programs += 1
            # Chunked-prefill buckets: every value _pad_chunk can return.
            row = jnp.asarray(self._pad_table([]), jnp.int32)
            buckets = sorted(
                {self._pad_chunk(n)
                 for n in range(1, self.prefill_chunk_tokens + 1)}
            )
            for b in buckets:
                chunk_args = (
                    jnp.asarray(0, jnp.int32),          # slot
                    row,                                 # all-sentinel table
                    # Built exactly like the live dispatch site (python
                    # list -> asarray): the weak-type strip is its own
                    # tiny convert_element_type program per bucket shape,
                    # and it must be warm too.
                    jnp.asarray([[0] * b], jnp.int32),   # tokens
                    jnp.asarray(0, jnp.int32),           # n_valid: no writes
                    jnp.asarray(0, jnp.int32),           # start
                    jnp.asarray(0, jnp.int32),           # budget
                    jnp.asarray(1.0, jnp.float32),
                    jnp.asarray(1.0, jnp.float32),
                )
                self._rng, sub = jax.random.split(self._rng)
                self.state, _ = self._chunk_fn(b)(
                    self.params, self.state, *chunk_args, sub,
                    jnp.asarray(False, bool),
                )
                programs += 1
                if self._lora is not None:
                    self._rng, sub = jax.random.split(self._rng)
                    self.state, _ = self._chunk_fn(b, lora=True)(
                        self.params, self.state, *chunk_args, sub,
                        jnp.asarray(False, bool),
                        jnp.asarray(0, jnp.int32), self._lora.bank,
                    )
                    programs += 1
                if self._spec:
                    self._rng_draft, dsub = jax.random.split(self._rng_draft)
                    self._draft_state, _ = self._draft_chunk_fn(b)(
                        self._draft_params, self._draft_state, *chunk_args,
                        dsub, jnp.asarray(False, bool),
                    )
                    programs += 1
            # Speculation ladder: every draft length the per-slot
            # adaptation can reach.
            if self._spec:
                for k in range(1, self._spec_max_draft + 1):
                    self._rng_draft, dsub = jax.random.split(self._rng_draft)
                    dk, dv, drafts, qlogits = self._spec_draft_fn(k)(
                        self._draft_params, self._draft_state.k,
                        self._draft_state.v, self.state.block_tables,
                        self.state.lengths, self.state.last_token,
                        self.state.active, self.state.temperature,
                        self.state.top_p, dsub,
                    )
                    self._draft_state = self._draft_state._replace(k=dk, v=dv)
                    self._rng, vsub = jax.random.split(self._rng)
                    self.state, *_ = self._spec_verify_fn(k)(
                        self.params, self.state, drafts, qlogits, vsub
                    )
                    programs += 2
                    if self._lora is not None:
                        self._rng, vsub = jax.random.split(self._rng)
                        self.state, *_ = self._spec_verify_fn(k, lora=True)(
                            self.params, self.state, drafts, qlogits, vsub,
                            self._lora.bank,
                        )
                        programs += 1
                self._draft_state = self._copy_draft_block(
                    self._draft_state, 0, 0
                )
                programs += 1
            # Table-row setter + CoW block copy (block 0 onto itself).
            self.state = self.state._replace(
                block_tables=self._set_table_row(
                    self.state.block_tables, jnp.asarray(0, jnp.int32), row
                )
            )
            self.state = self._copy_block(self.state, 0, 0)
            programs += 2
            # KV-transfer programs for this role's side of the seam.
            blk_pads = []
            n_pad = 1
            while n_pad < self._max_blocks:
                blk_pads.append(n_pad)
                n_pad <<= 1
            blk_pads.append(n_pad)
            if self.role == "prefill":
                for n_pad in blk_pads:
                    ids = jnp.full((n_pad,), self._num_blocks, jnp.int32)
                    toks = self._gather_blocks_fn(n_pad)(self.state.k, ids)
                    programs += 1
            if self.role == "decode":
                for n_pad in blk_pads:
                    ids = jnp.full((n_pad,), self._num_blocks, jnp.int32)
                    payload = jnp.zeros(
                        self.state.k.shape[:1] + (n_pad,)
                        + self.state.k.shape[2:], self.state.k.dtype,
                    )
                    self.state = self.state._replace(
                        k=self._inject_blocks_fn(n_pad, draft=False)(
                            self.state.k, ids, payload
                        )
                    )
                    programs += 1
                    if self._spec:
                        dpayload = jnp.zeros(
                            self._draft_state.k.shape[:1] + (n_pad,)
                            + self._draft_state.k.shape[2:],
                            self._draft_state.k.dtype,
                        )
                        self._draft_state = self._draft_state._replace(
                            k=self._inject_blocks_fn(n_pad, draft=True)(
                                self._draft_state.k, ids, dpayload
                            )
                        )
                        programs += 1
                self._place_slot(0, [], 0, 0, 0, 1.0, 1.0, -1)
                programs += 1
            jax.block_until_ready(self.state.lengths)
            if self._spec:
                jax.block_until_ready(self._draft_state.k)
            auto_stage("compile_end")
        finally:
            with self._lock:
                self._hold_admission = False
            self._wake.set()
        dt = time.monotonic() - t0
        after = compile_cache.snapshot()
        self._warmup_seconds = dt
        self._warmup_programs = programs
        self._warmup_hist.observe(dt)
        self._warmup_done = True
        self._cold_over = True
        auto_stage("warmup_end")
        return {
            "seconds": dt,
            "programs": programs,
            "compiles": after["compiles"] - before["compiles"],
            "cache_hits": after["cache_hits"] - before["cache_hits"],
            "cache_misses": after["cache_misses"] - before["cache_misses"],
            "compile_seconds": round(
                after["compile_seconds"] - before["compile_seconds"], 4
            ),
        }

    def submit(
        self,
        tokens: List[int],
        max_new_tokens: int,
        temperature: Optional[float] = None,
        top_p: float = 1.0,
        request_id: Optional[int] = None,
        adapter: Optional[str] = None,
        traceparent: Optional[str] = None,
        x_request_id: Optional[str] = None,
        t_arrival: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> "queue.Queue[object]":
        """Enqueue a request; returns its output queue (see _Request.out
        for the token/None/Exception protocol). `temperature` (0 =
        greedy) and `top_p` (nucleus cutoff, 1 = no filtering) override
        the engine defaults for THIS request — requests with different
        sampling params share one decode batch. `adapter` selects a
        loaded LoRA adapter by name (multi-tenant engines only); the
        request holds a registry ref until it retires, so the adapter
        cannot be evicted or unloaded under it.

        `traceparent`/`x_request_id` thread the caller's trace identity
        into the flight recorder (and onto the KV handoff for split
        requests); `t_arrival` backdates the timeline to HTTP arrival so
        server-side admission (QoS gate) shows up as its own phase.

        `tenant` keys the engine's qos_weights map: on a host-tier
        engine a heavier tenant's request may preempt a lighter one's
        live slot (swap-out to host, resume later) instead of queueing
        behind it."""
        if not tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature is None:
            temperature = self._temperature
        import math

        # `not (>= 0)` also rejects NaN (which would silently decode
        # greedy); inf would flatten logits to uniform-vocab garbage.
        if not (temperature >= 0) or math.isinf(temperature):
            raise ValueError(
                f"temperature must be a finite number >= 0, got {temperature}"
            )
        if not (0 < top_p <= 1):  # also rejects NaN
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # The last decode write lands at cache row len + max_new - 2, so
        # len + max_new == max_len exactly fills the cache.
        if len(tokens) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(tokens)} + max_new_tokens {max_new_tokens}"
                f" must not exceed max_len {self.max_len}"
            )
        # Worst-case block demand (no prefix hit) must fit the pool, or
        # the request could stall admission forever on a small pool.
        need = (len(tokens) + max_new_tokens - 2) // self._block_size + 1
        if need > self._num_blocks:
            raise ValueError(
                f"request needs up to {need} KV blocks but the pool has"
                f" {self._num_blocks} (raise kv_pool_blocks)"
            )
        out: "queue.Queue[object]" = queue.Queue()
        # Open the request's timeline before admission so a shed request
        # still leaves a (terminal) trace for tail capture. With a
        # backdated arrival the gap to submit is the qos_admission phase.
        t_sub = time.monotonic()
        rec = None
        if self.recorder.enabled:
            first = ("qos_admission" if t_arrival is not None
                     else "adapter_acquire" if adapter is not None
                     else "queue_wait")
            rec = self.recorder.begin(
                request_id, x_request_id=x_request_id,
                traceparent=traceparent, first_phase=first,
                t0=t_sub if t_arrival is None else t_arrival,
            )
            if t_arrival is not None:
                rec.mark(
                    "adapter_acquire" if adapter is not None
                    else "queue_wait", t_sub,
                )
        with self._lock:
            if self._failed is not None:
                raise RuntimeError(f"serving engine failed: {self._failed}")
            if self._stop:
                raise RuntimeError("serving engine is closed")
            depth = self._pending.qsize() + (self._next_req is not None)
            # Shed on the WAITING backlog, not raw queue depth: a request
            # that will land in a currently-free slot is not overload
            # (and max_pending=0 then means "serve, never queue" instead
            # of bricking an idle engine). The snapshot is consistent:
            # the loop thread mutates _live and _admitting under this
            # same lock, and clears a retiring slot BEFORE signalling its
            # consumer — so a client that saw its stream end and
            # immediately resubmits cannot be shed by a stale free count.
            # Requests in the chunked-prefill window (_admitting) are in
            # neither _pending nor _live but do occupy capacity.
            free = sum(r is None for r in self._live) - len(self._admitting)
            backlog = depth - free
            if self.max_pending is not None and backlog >= self.max_pending:
                self.rejected += 1
                self.recorder.finish(rec, "shed")
                raise EngineOverloadedError(depth, self._retry_after(depth))
            adapter_ix = -1
            if adapter is not None:
                if self._lora is None:
                    raise ValueError(
                        "engine has no adapter support"
                        " (construct with lora_max_adapters > 0)"
                    )
                # Raises KeyError for unknown adapters BEFORE anything is
                # queued; the ref pins the pool slot until the request
                # retires (_release_adapter at every terminal path).
                adapter_ix = self._lora.acquire(adapter)
                self._adapter_holds[out] = adapter
                if rec is not None:
                    rec.mark("queue_wait")  # adapter_acquire closes here
            self._pending.put(
                _Request(list(tokens), max_new_tokens, out,
                         float(temperature), float(top_p), time.monotonic(),
                         request_id, adapter, adapter_ix, traceparent, rec,
                         tenant)
            )
            self._inflight.add(out)
        self._wake.set()
        return out

    def _retry_after(self, depth: int) -> float:
        """Estimated seconds until this caller would likely be admitted:
        the queue ahead of it drains one slot-batch per measured
        slot-turn (admit -> retire, EWMA over completed requests)."""
        turns_ahead = (depth + 1) / max(1, self.slots)
        return max(1.0, round(turns_ahead * self._turn_s, 1))

    def cancel(self, out: "queue.Queue[object]") -> None:
        """Abandon the request whose submit() returned `out` — the slot
        (or pending entry) is freed at the next chunk boundary. Safe from
        any thread; idempotent; unknown queues are ignored. The consumer
        receives the clean-end None once the loop processes it (a
        still-queued request is purged and answered immediately)."""
        with self._lock:
            if out not in self._inflight:
                return
            # Purge a still-QUEUED request right here rather than leaving
            # a tombstone: dead entries would keep counting in the
            # admission backlog and stats()["pending"], shedding new
            # traffic below the real max_pending bound under cancel-heavy
            # load (disconnecting clients cancel from a finally:).
            # queue.Queue is internally locked, so draining interleaves
            # safely with the loop thread's get_nowait; order of the
            # survivors is preserved.
            drained, found = [], None
            while True:
                try:
                    r = self._pending.get_nowait()
                except queue.Empty:
                    break
                if r.out is out:
                    found = r
                else:
                    drained.append(r)
            for r in drained:
                self._pending.put(r)
            if found is not None:
                self._inflight.discard(out)
                self._release_adapter(out)
                self.recorder.finish(found.trace, "cancelled")
                out.put(None)
                return
            # Swapped-out slot (cancel mid-swap): purge the parked
            # payload and unpin its host bytes right here — zero residue
            # on the host tier is the same invariant as zero device
            # blocks for a retired slot.
            for i, sw in enumerate(self._swapped):
                if sw.req.out is out:
                    self._swapped.pop(i)
                    if self._host_tier is not None:
                        self._host_tier.unreserve(sw.nbytes)
                    self._inflight.discard(out)
                    self._release_adapter(out)
                    self.recorder.finish(sw.req.trace, "cancelled")
                    out.put(None)
                    return
            if self._next_req is not None and self._next_req.out is out:
                req = self._next_req
                self._next_req = None
                self._inflight.discard(out)
                self._release_adapter(out)
                self.recorder.finish(req.trace, "cancelled")
                out.put(None)
                return
            self._cancelled.add(out)
        self._wake.set()

    def preempt(self, out: "queue.Queue[object]") -> None:
        """Ask the engine to preempt the LIVE request whose submit()
        returned `out` at the next chunk boundary: its block chain swaps
        out to the host tier and the request readmits later (resuming
        bit-exact at temperature 0). Advisory — a request that is not
        live, an engine without a host tier, or a host budget that can't
        pin the payload leaves the request running. Safe from any
        thread; idempotent."""
        if self._host_tier is None:
            return
        with self._lock:
            if out in self._inflight:
                self._preempt_requests.add(out)
        self._wake.set()

    # -- multi-tenant adapters ----------------------------------------------

    @property
    def lora_enabled(self) -> bool:
        return self._lora is not None

    def _require_lora(self):
        if self._lora is None:
            raise RuntimeError(
                "engine has no adapter support"
                " (construct with lora_max_adapters > 0)"
            )
        return self._lora

    def load_adapter(self, name: str, adapter: Params, *,
                     alpha: float = 16.0) -> int:
        """Install (or replace) a LoRA adapter under `name`; returns its
        device pool slot. May LRU-evict an idle adapter under slot
        pressure; raises AdapterBusyError / AdapterPoolFullError when
        in-flight refs forbid it (lora_serving)."""
        with self._lock:
            return self._require_lora().load(name, adapter, alpha=alpha)

    def unload_adapter(self, name: str) -> None:
        with self._lock:
            self._require_lora().unload(name)

    def adapters(self) -> Dict[str, Dict[str, Any]]:
        """Loaded adapters: name -> {slot, refs, alpha, rank}."""
        with self._lock:
            return {} if self._lora is None else self._lora.loaded()

    def affinity_sketch(self, limit: int = 512) -> Dict[str, Any]:
        """Cache-affinity sketch for fleet routing: the bounded set of
        resident prefix chain-head digests (device pool + host tier,
        namespace-seeded exactly as BlockAllocator._ns_seed chains them)
        plus the loaded-adapter set. A router that recomputes the same
        chain over the same block-size boundaries can score this replica
        by expected matched blocks without touching the engine. Bounded
        and O(cached blocks); taken under the engine lock so the digest
        set is a consistent snapshot of the allocator."""
        with self._lock:
            device = self._alloc.affinity_digests(limit)
            host = (
                self._host_tier.affinity_digests(limit)
                if self._host_tier is not None else []
            )
            adapters = [] if self._lora is None else sorted(self._lora.loaded())
        # Device digests win the bound (they serve a match without a
        # swap-in); host-tier digests fill whatever room remains. Order
        # is irrelevant to the router — it scores by set membership.
        seen = set(device)
        merged = (device + [d for d in host if d not in seen])[:limit]
        return {
            "block_size": self._block_size,
            "digests": merged,
            "adapters": adapters,
        }

    def _release_adapter(self, out) -> None:
        """Drop a request's adapter ref (idempotent; caller holds _lock).
        Every terminal path — retire, cancel, drop, force-retire, flush —
        funnels through here so refcounts cannot leak and pin pool slots."""
        name = self._adapter_holds.pop(out, None)
        if name is not None and self._lora is not None:
            self._lora.release(name)

    def stats(self) -> Dict[str, Any]:
        """Live load snapshot (feeds /metrics and autoscaler signals).

        Beyond queue/shed counters and the scheduler gauges (`ttft_
        seconds_ewma` with its queue-wait/prefill breakdown, the loop's
        own time per phase as `loop_*_seconds_total` with the decode
        step/slot-step/token counters), this now reports the paged-KV
        view: pool occupancy (`kv_blocks_in_use` /
        `kv_blocks_cached` of `kv_blocks_total`), prefix-cache hit
        counters with `prefix_tokens_reused_total` (prompt tokens whose
        prefill was skipped), copy-on-write and eviction counters, and
        the chunked-prefill counters (`prefill_chunks_total`,
        `prefill_tokens_computed_total` — diff the latter across a
        window against submitted prompt tokens to measure the prefill
        compute saved by sharing)."""
        loop = self._clock.snapshot()
        phase_s = loop["seconds"]
        live_blocks, dead_blocks = self._layer_blocks()
        a = self._alloc
        tier = (
            self._host_tier.stats() if self._host_tier is not None else {}
        )
        cc = compile_cache.snapshot()
        return {
            "slots": self.slots,
            "active": sum(r is not None for r in self._live),
            "pending": self._pending.qsize() + (self._next_req is not None),
            "max_pending": self.max_pending,
            "rejected_total": self.rejected,
            "chunk_seconds_ewma": round(self._chunk_s, 4),
            "slot_turn_seconds_ewma": round(self._turn_s, 3),
            "steps_per_sync": self._steps_per_sync,
            "max_prefills_per_chunk": self.max_prefills_per_chunk,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "kv_block_size": self._block_size,
            "kv_blocks_total": a.num_blocks,
            "kv_blocks_in_use": a.in_use,
            "kv_blocks_cached": a.cached,
            "prefix_cache_hits_total": a.hits,
            "prefix_cache_misses_total": a.misses,
            # Hit-tier split: a "host hit" is a prefix match that pulled
            # at least one block back from the host tier (swap-in); the
            # remainder of `hits` served entirely from device blocks.
            # device + host + misses partitions every match() probe.
            "prefix_cache_device_hits_total": a.hits - a.host_hits,
            "prefix_cache_host_hits_total": a.host_hits,
            "prefix_tokens_reused_total": a.tokens_reused,
            "kv_cow_copies_total": a.cow_copies,
            "kv_block_evictions_total": a.evictions,
            # Hierarchical KV: host-tier occupancy + flow counters (all
            # zero without kv_host_budget_bytes) and the slot-preemption
            # view — swapped slots are admitted streams NOT currently
            # resident in HBM, the overcommit the tier buys.
            "kv_host_enabled": self._host_tier is not None,
            "kv_host_budget_bytes": tier.get("budget_bytes", 0),
            "kv_host_blocks": tier.get("blocks", 0),
            "kv_host_bytes": (
                tier.get("spill_bytes", 0) + tier.get("pinned_bytes", 0)
            ),
            "kv_spills_total": tier.get("spills_total", 0),
            "kv_host_evictions_total": tier.get("evictions_total", 0),
            "kv_swap_ins_total": tier.get("swap_ins_total", 0),
            "max_resident_slots": self._max_resident,
            "slots_swapped": len(self._swapped),
            "slot_preemptions_total": self._preemptions,
            "slot_swap_ins_total": self._slot_swap_ins,
            "swap_in_hist": self._swap_in_hist.to_dict(),
            "prefill_chunks_total": self._prefill_chunks,
            "prefill_tokens_computed_total": self._prefill_tokens_computed,
            "ttft_seconds_ewma": round(self._ttft_s, 4),
            "queue_wait_seconds_ewma": round(self._queue_wait_s, 4),
            "prefill_seconds_ewma": round(self._prefill_s, 4),
            # The loop's own time (PhaseClock): host wall seconds per
            # phase, whole cycles only and unrounded, so at any read the
            # phases but `wait` sum to loop_cycle_seconds_total.
            # Monotonic; diff two snapshots for a window's shares.
            "loop_cycles_total": loop["cycles"],
            "loop_cycle_seconds_total": loop["cycle_seconds"],
            **{name: phase_s[key]
               for key, name in _LOOP_SECONDS_KEYS.items()},
            # Cycles of SLOW_CYCLE_SECONDS or more, and the last few of
            # them whole ({t, seconds, phases, live, tasks, pending}):
            # where a stalled run is to be read.
            "loop_slow_cycles_total": loop["slow_cycles"],
            "loop_slow_cycle_seconds_total": loop["slow_cycle_seconds"],
            "loop_slow_cycles": loop["slow"],
            # Work at the scheduler's boundary: steps of every launched
            # decode chunk or speculation round, the same times the slots
            # live at launch, and the tokens those steps emitted.
            "decode_steps_total": self._decode_steps,
            "decode_slot_steps_total": self._decode_slot_steps,
            "decode_tokens_total": self._decode_tokens,
            # The live slots' blocks at each launched step over the block
            # table's columns: the share of the table paged attention has
            # anything to read in.
            "decode_live_blocks_total": self._decode_live_blocks,
            "decode_table_columns_total": self._decode_table_columns,
            # The same blocks layer by layer (live blocks x layers), the
            # columns the layers' walks visit (a window layer's start at
            # the first block its window reaches) and the window layers'
            # blocks behind it, which no later step reads and the one pool
            # keeps: summed over launched steps, and as they stand now.
            "decode_layer_blocks_total": self._decode_layer_blocks,
            "decode_attended_blocks_total": self._decode_attended_blocks,
            "decode_window_dead_blocks_total":
                self._decode_window_dead_blocks,
            "kv_layer_blocks": live_blocks * self.config.n_attn_layers,
            "kv_window_dead_blocks": dead_blocks,
            # The older three-way split, derived from the same clock:
            # launch to readback; admission host work (with the barrier
            # of a cycle that had nothing live); waiting for work. They
            # leave out barrier and fan-out of decoding cycles.
            "decode_seconds_total": round(
                phase_s["dispatch"] + phase_s["sync"], 6),
            "prefill_seconds_total": round(
                phase_s["admit"] + phase_s["grow"]
                + self._admission_barrier_s, 6),
            "idle_seconds_total": round(phase_s["wait"], 6),
            # Summary-style sum/count behind the latency EWMAs: diff two
            # snapshots for an exact per-window mean (the EWMAs carry
            # compile-spike history across windows; these don't).
            "admitted_total": self._n_admitted,
            "ttft_seconds_sum": round(self._sum_ttft, 4),
            "queue_wait_seconds_sum": round(self._sum_queue_wait, 4),
            "prefill_seconds_sum": round(self._sum_prefill, 4),
            # Bucketed TTFT ({"buckets": [(le, cumulative)...], "sum",
            # "count"}) — prometheus_metrics renders the histogram series.
            "ttft_hist": self._ttft_hist.to_dict(),
            # Cold-start split of the same series (role="cold_start"):
            # the first token a warmup-less boot delivered, i.e. the
            # sample that paid compilation. Empty on warmup-gated boots.
            "ttft_cold_hist": self._ttft_cold_hist.to_dict(),
            # Cold-start fast path (PR 20): warmup coverage + the
            # process-wide compile/persistent-cache counters behind the
            # zero-post-ready-compile readiness contract.
            "warmup_done": self._warmup_done,
            "warmup_seconds": (
                None if self._warmup_seconds is None
                else round(self._warmup_seconds, 4)
            ),
            "warmup_programs": self._warmup_programs,
            "warmup_hist": self._warmup_hist.to_dict(),
            "compile_cache_dir": self._compile_cache_dir,
            "compiles_total": cc["compiles"],
            "compile_cache_hits_total": cc["cache_hits"],
            "compile_cache_misses_total": cc["cache_misses"],
            # Seconds actually spent inside backend compilation (cache
            # retrievals report their own, much smaller, durations): the
            # cost the persistent cache removes. Wall-clock warmup spans
            # conflate it with tracing/lowering, which no cache can skip.
            "compile_seconds_total": round(cc["compile_seconds"], 4),
            # Disaggregation: which half of the split this engine is
            # (TTFT/TPT series carry it as a role label — the legs of a
            # split request are different quantities and must not be
            # aggregated into one distribution), plus the KV handoff
            # counters on both sides of the transfer seam.
            "role": self.role,
            "handoff_epoch": self.handoff_epoch,
            "kv_handoffs_sent_total": self._handoffs_sent,
            "kv_handoffs_received_total": self._handoffs_received,
            "kv_handoffs_stale_rejected_total": self._handoff_stale_rejected,
            "kv_transfer_bytes_total": self._kv_transfer_bytes,
            "kv_transfer_hist": self._kv_transfer_hist.to_dict(),
            "kv_transfer_queue_depth": (
                self._handoff_q.qsize() + len(self._prefilled_pending)
            ),
            "tpt_hist": self._tpt_hist.to_dict(),
            # Speculative decoding: per-round draft/verify wall time,
            # token fate counters (proposed = accepted + rejected; the
            # bonus/correction token the target emits each round is NOT
            # counted as proposed), and the acceptance EWMAs that drive
            # per-slot draft-length adaptation and whole-batch fallback.
            "spec_enabled": self._spec,
            "spec_max_draft": self._spec_max_draft,
            "spec_rounds_total": self._spec_rounds,
            "spec_fallback_rounds_total": self._spec_fallback_rounds,
            "spec_tokens_proposed_total": self._spec_proposed,
            "spec_tokens_accepted_total": self._spec_accepted,
            "spec_tokens_rejected_total": self._spec_rejected,
            "spec_accept_rate_ewma": round(self._spec_accept_ewma, 4),
            "spec_tokens_per_round_ewma": round(
                self._spec_tokens_round_ewma, 4
            ),
            "spec_draft_len_mean": round(
                sum(self._slot_k) / len(self._slot_k), 4
            ) if self._slot_k else 0.0,
            "spec_draft_seconds_total": round(self._t_spec_draft, 4),
            "spec_verify_seconds_total": round(self._t_spec_verify, 4),
            # Ragged-attention dispatch: which implementation this
            # engine's geometry selects (static) and how many jitted
            # programs ran it (chunk prefills, decode chunks, spec
            # draft/verify forwards).
            "attn_path": self._attn_path + (
                "_latent" if self.config.latent else ""
            ),
            # One period of the layer pattern, a letter a layer: w for a
            # sliding-attention layer of `sliding_window` keys, f for full;
            # a model with state-space layers writes m for those and a for
            # its attention layers.
            "layer_pattern": "".join(
                "m" if kind == MAMBA
                else "a" if self.config.has_state_layers
                else "w" if self.config.window(kind) else "f"
                for kind in self.config.layer_period
            ),
            "sliding_window": self.config.sliding_window,
            # Bytes one cached token allocates per layer (padding
            # included), and the expert slots routed vs computed.
            "kv_row_bytes": self.config.kv_row_bytes(),
            # Layers that keep rows (the KV pool's layer axis), and beside
            # the rows the recurrent state of a model with state-space
            # layers: bytes a slot at any context, the pool's bytes, and
            # the state rows (slot x state layer) the launched decode
            # steps needed against those their programs moved.
            "kv_pool_layers": self.config.n_attn_layers,
            "state_row_bytes": self.config.state_row_bytes(),
            "state_pool_bytes": self.slots * self.config.state_row_bytes(),
            "decode_state_rows_total": self._decode_state_rows,
            "decode_state_rows_computed_total":
                self._decode_state_rows_computed,
            # "on", or why prefix reuse is off; the recurrence's form
            # ("pallas", "lax"; "none": no state-space layer).
            "prefix_cache": self._prefix_cache,
            "scan_path": self._scan_path,
            # The bank held here (all the experts routed over, or a
            # device's share of them) and what was asked of it: the (token,
            # expert) pairs routing chose, those that fell on an expert
            # held (all of them on a whole bank: counted at the launch
            # from shapes; on a share the programs count them on the
            # device and a decode chunk's sync reads them), and the slots
            # the launched programs multiplied for them.
            "moe_experts_published": self.config.n_experts,
            "moe_experts_held": self.config.held[1] if self.config.n_experts else 0,
            "moe_pairs_total": self._moe_pairs,
            "moe_local_pairs_total": self._moe_local_pairs,
            "moe_routed_slots_total": self._moe_local_pairs,
            "moe_computed_slots_total": self._moe_computed_slots,
            "moe_routed_launches_total": self._moe_routed_launches,
            "moe_expert_launches_total": self._moe_expert_launches,
            "attn_dispatch_pallas_total": self._attn_dispatch["pallas"],
            "attn_dispatch_lax_ragged_total":
                self._attn_dispatch["lax_ragged"],
            # Multi-tenant LoRA: pool occupancy for the adapters_loaded
            # gauge and capacity dashboards.
            "lora_enabled": self._lora is not None,
            "lora_max_adapters": (
                0 if self._lora is None else self._lora.max_adapters
            ),
            "adapters_loaded": (
                0 if self._lora is None else self._lora.loaded_count
            ),
            # Per-request flight recorder (PR 15): ring occupancy/tail
            # counters plus the per-phase latency histograms behind
            # dstack_tpu_serving_phase_seconds.
            "trace": self.recorder.stats(),
            "phase_hists": self.recorder.phase_histograms(),
            # Cache-affinity sketch (PR 18): resident prefix chain-head
            # digests + loaded adapters, the payload fleet routers score
            # replicas by (also served on GET /v1/affinity).
            "affinity": self.affinity_sketch(),
        }

    def request_trace(self, key: Any) -> Optional[Dict[str, Any]]:
        """Phase-timeline snapshot for one request, by engine request id
        or client X-Request-ID (None when unknown, recycled, or the
        recorder is off) — the payload behind GET /v1/requests/<id>/trace."""
        return self.recorder.get(key)

    def close(self) -> None:
        with self._lock:
            self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)
        self._deliver_q.put(None)
        self._deliver_thread.join(timeout=10)
        if self._handoff_thread is not None:
            self._handoff_q.put(None)
            self._handoff_thread.join(timeout=10)
        # Requests still in flight get an exception, not the clean-end
        # None: a consumer must not mistake a truncated generation for a
        # complete one (same principle _flush_all states for failures).
        self._flush_all(RuntimeError("serving engine closed mid-generation"))

    def _flush_all(self, error: Optional[BaseException]) -> None:
        """Terminate every consumer: no out.get() may hang forever. A
        failure is delivered as the exception itself, NOT the clean-end
        None — partial output must not read as success."""
        sentinel: object = error if error is not None else None
        # A settled chunk's tokens are whole and its ended slots are
        # nobody's any more: hand them out before anything is cut short.
        chunk, self._settled, self._chunk = self._settled, None, None
        if chunk is not None:
            self._hand_out(chunk)
        with self._lock:
            self._cancelled.clear()
            self._inflight.clear()
            # Every in-flight adapter ref dies with its consumer.
            if self._lora is not None:
                for name in self._adapter_holds.values():
                    self._lora.release(name)
            self._adapter_holds.clear()
            for slot, req in enumerate(self._live):
                if req is not None:
                    self.recorder.finish(req.trace, "error")
                    req.out.put(sentinel)
                    self._live[slot] = None
            # Requests caught mid-chunked-prefill (popped from _pending,
            # not yet live) must get the sentinel too, or their consumers
            # hang forever on a dead engine.
            for req in self._admitting:
                self.recorder.finish(req.trace, "error")
                req.out.put(sentinel)
            self._admitting.clear()
            self._tasks.clear()
            self._pending_activation.clear()
            # Swapped-out slots and the admission peek buffer hold
            # consumers too (their requests are neither pending nor live).
            for sw in self._swapped:
                self.recorder.finish(sw.req.trace, "error")
                sw.req.out.put(sentinel)
                if self._host_tier is not None:
                    self._host_tier.unreserve(sw.nbytes)
            self._swapped.clear()
            self._preempt_requests.clear()
            if self._next_req is not None:
                self.recorder.finish(self._next_req.trace, "error")
                self._next_req.out.put(sentinel)
                self._next_req = None
            # Handoffs queued but not yet admitted (decode role): their
            # consumers are waiting on the stream too.
            for _h, h_out, _t, h_rec in self._prefilled_pending:
                self.recorder.finish(h_rec, "error")
                h_out.put(sentinel)
            self._prefilled_pending.clear()
            while True:
                try:
                    r = self._pending.get_nowait()
                except queue.Empty:
                    return
                self.recorder.finish(r.trace, "error")
                r.out.put(sentinel)

    # -- chunked prefill admission -------------------------------------------

    def _chunk_fn(self, n_padded: int, lora: bool = False):
        """The jitted chunk-prefill program for padded chunk length
        `n_padded` (one compile per pow-2 bucket, per LoRA flavor —
        prefill is per-request, so an adapter-free request on a LoRA
        engine uses the plain program). Tests monkeypatch this to block
        or spy on chunk dispatches."""
        fn = self._chunk_cache.get((n_padded, lora))
        if fn is None:
            fn = make_chunk_prefill(
                self.config, n_padded, shardings=self._shardings,
                lora=lora, attn_impl=self._attn_path,
            )
            self._chunk_cache[(n_padded, lora)] = fn
        return fn

    def _draft_chunk_fn(self, n_padded: int):
        """Drafter twin of _chunk_fn (the drafter config compiles its
        own bucket entries)."""
        fn = self._draft_chunk_cache.get(n_padded)
        if fn is None:
            fn = make_chunk_prefill(
                self._draft_config, n_padded,
                shardings=self._draft_shardings,
                attn_impl=self._draft_attn_path,
            )
            self._draft_chunk_cache[n_padded] = fn
        return fn

    def _spec_draft_fn(self, k: int):
        fn = self._spec_draft_fns.get(k)
        if fn is None:
            fn = make_spec_draft(
                self._draft_config, k, shardings=self._draft_shardings,
                attn_impl=self._draft_attn_path,
            )
            self._spec_draft_fns[k] = fn
        return fn

    def _spec_verify_fn(self, k: int, lora: bool = False):
        fn = self._spec_verify_fns.get((k, lora))
        if fn is None:
            fn = make_spec_verify(
                self.config, k, shardings=self._shardings,
                lora=lora, attn_impl=self._attn_path,
            )
            self._spec_verify_fns[(k, lora)] = fn
        return fn

    def _pad_chunk(self, n: int) -> int:
        """Pow-2 bucket (min 8) capped at the chunk budget, so compile
        entries stay O(log prefill_chunk_tokens)."""
        c = 8
        while c < n:
            c *= 2
        return max(min(c, self.prefill_chunk_tokens), n)

    def _pad_table(self, table: List[int]) -> List[int]:
        """Pad a host table to the device row width with the OOB sentinel
        (num_blocks): padded gathers clip (masked garbage), padded
        scatters drop — never block 0."""
        return table + [self._num_blocks] * (self._max_blocks - len(table))

    def _drop_task(self, task: _PrefillTask) -> None:
        """Abandon a mid-prefill task (cancel): release its blocks,
        answer the consumer, clear admission accounting."""
        with self._lock:
            for b in task.table:
                self._alloc.release(b)
            task.table.clear()
            self._cancelled.discard(task.req.out)
            self._inflight.discard(task.req.out)
            if task.req in self._admitting:
                self._admitting.remove(task.req)
            self._release_adapter(task.req.out)
        self._tasks.remove(task)
        self.recorder.finish(task.req.trace, "cancelled")
        task.req.out.put(None)

    def _ensure_task_blocks(self, task: _PrefillTask, upto: int) -> bool:
        """Make blocks [pos//bs, (upto-1)//bs] of the task's table
        writable: fresh-allocate missing ones, copy-on-write shared ones.
        False (and no dispatch this boundary) when the pool is exhausted
        — refs already taken are kept, so the retry resumes where it
        stalled."""
        bs = self._block_size
        first_blk = task.pos // bs
        last_blk = (upto - 1) // bs
        with self._lock:
            for idx in range(first_blk, last_blk + 1):
                if idx < len(task.table):
                    b, needs_copy = self._alloc.ensure_writable(task.table[idx])
                    if b is None:
                        return False
                    if needs_copy:
                        src = jnp.asarray(task.table[idx], jnp.int32)
                        dst = jnp.asarray(b, jnp.int32)
                        self.state = self._copy_block(self.state, src, dst)
                        if self._spec:
                            # One allocator, two pools: the drafter's
                            # copy of the shared block moves with it.
                            self._draft_state = self._copy_draft_block(
                                self._draft_state, src, dst
                            )
                        task.table[idx] = b
                else:
                    b = self._alloc.alloc()
                    if b is None:
                        return False
                    task.table.append(b)
        return True

    def _advance_prefills(self, budget: int) -> Tuple[bool, int]:
        """One admission boundary: pull new requests into prefill tasks
        (up to `max_prefills_per_chunk` concurrent, prefix-cache matched
        on entry), then dispatch prompt chunks round-robin within a
        TOTAL `budget` of valid tokens (a cycle's is
        `prefill_chunk_tokens`) — so one long prompt and eight short
        ones cost a decode stream the same bounded stall. Dispatch-only
        (no host sync): the jitted final chunk samples the first token
        and flips the slot live on device; the reader thread picks the
        token up the moment its readback lands. Safe while a decode
        chunk is in flight: it takes free slots, and slots that chunk
        is sure to end (`_Chunk.ending`; the host side of such a slot
        changes hands when the chunk is settled, `_Chunk.heirs`), and
        moves no live slot (`_try_queue_jump` refuses until the
        boundary). Returns
        (anything moved: admission, dispatch or cancel processing;
        prompt tokens launched)."""
        progressed = False
        offered = budget
        in_flight = self._chunk
        ending = in_flight.ending if in_flight is not None else set()
        # Admit new requests into the task window (unless a gang-
        # synchronous caller is holding admission to batch a round of
        # submits into one wave; in-flight tasks keep dispatching).
        while (not self._hold_admission
               and len(self._tasks) < self.max_prefills_per_chunk):
            busy = {t.slot for t in self._tasks}
            with self._lock:
                req = self._next_req
                self._next_req = None
            if req is None:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
            with self._lock:
                dead = req.out in self._cancelled
                if dead:
                    # abandoned while queued: never occupy a slot
                    self._cancelled.discard(req.out)
                    self._inflight.discard(req.out)
                    self._release_adapter(req.out)
            if dead:
                self.recorder.finish(req.trace, "cancelled")
                req.out.put(None)
                progressed = True
                continue

            def _room():
                # Residency cap: a prefilling task goes live the moment
                # it finalizes, so it counts against max_resident_slots
                # now. Swapped-out slots deliberately do NOT count —
                # their KV lives host-side. A slot sure to end in the
                # decode chunk in flight is as good as free: what is
                # launched for it now runs on the device after that
                # chunk (empty slots are taken first).
                live_n = sum(r is not None and s not in ending
                             for s, r in enumerate(self._live))
                if live_n + len(busy) >= self._max_resident:
                    return []
                return [s for s in range(self.slots)
                        if self._live[s] is None and s not in busy
                        ] + sorted(ending - busy)

            free = _room()
            if not free:
                # Every resident slot taken: a heavier tenant may
                # queue-jump by swapping the lightest live slot out
                # (freeing both the slot and its residency); otherwise
                # the head request parks in the peek buffer (still
                # counted as backlog) until a slot frees.
                if self._try_queue_jump(req):
                    progressed = True
                    free = _room()
                if not free:
                    with self._lock:
                        self._next_req = req
                    break
            with self._lock:
                self._admitting.append(req)
                with self._clock.child("match", request_id=_span_id(req),
                                       tokens=len(req.tokens)):
                    blocks, matched = self._alloc.match(
                        req.tokens, namespace=(req.adapter or "").encode()
                    )
            slot = free[0]
            t_pop = time.monotonic()
            self._queue_wait_s = self._ewma_seed(
                self._queue_wait_s, t_pop - req.t_submit
            )
            self._sum_queue_wait += t_pop - req.t_submit
            if req.trace is not None:
                req.trace.mark("prefill", t_pop)  # queue_wait closes here
            self._tasks.append(_PrefillTask(req, slot, matched, blocks, t_pop))
            progressed = True
        # Dispatch chunks under the shared token budget.
        for task in list(self._tasks):
            if budget <= 0:
                break
            with self._lock:
                dead = task.req.out in self._cancelled
            if dead:
                self._drop_task(task)
                progressed = True
                continue
            n = min(len(task.req.tokens) - task.pos, budget)
            if not self._ensure_task_blocks(task, task.pos + n):
                continue  # pool exhausted; retry next boundary
            final = task.pos + n == len(task.req.tokens)
            n_padded = self._pad_chunk(n)
            chunk = task.req.tokens[task.pos:task.pos + n]
            span = {"request_id": _span_id(task.req), "tokens": n}
            lora = self._lora is not None and task.req.adapter_ix >= 0
            with self._clock.child("chunk_args", **span):
                sub = self._next_key()
                chunk_args = (
                    jnp.asarray(task.slot, jnp.int32),
                    jnp.asarray(self._pad_table(task.table), jnp.int32),
                    jnp.asarray([chunk + [0] * (n_padded - n)], jnp.int32),
                    jnp.asarray(n, jnp.int32),
                    jnp.asarray(task.pos, jnp.int32),
                    jnp.asarray(task.req.max_new_tokens, jnp.int32),
                    jnp.asarray(task.req.temperature, jnp.float32),
                    jnp.asarray(task.req.top_p, jnp.float32),
                )
                final_arg = jnp.asarray(final, bool)
                if lora:
                    adapter_arg = jnp.asarray(task.req.adapter_ix, jnp.int32)
                if self._spec:
                    dsub = self._next_draft_key()
            with self._clock.child("chunk_launch", **span):
                if lora:
                    # Target-only: the drafter below never applies LoRA.
                    self.state, first = self._chunk_fn(n_padded, lora=True)(
                        self.params, self.state, *chunk_args, sub,
                        final_arg, adapter_arg, self._lora.bank,
                    )
                else:
                    self.state, first = self._chunk_fn(n_padded)(
                        self.params, self.state, *chunk_args, sub, final_arg,
                    )
                self._attn_dispatch[self._attn_path] += 1
                self._count_expert_slots(n, 1, n_padded)
                if self._spec:
                    # The drafter prefills the same chunk into ITS pool
                    # through the same table — prefix-cache hits skip both
                    # models' prefill identically (same task.pos start).
                    self._draft_state, _ = self._draft_chunk_fn(n_padded)(
                        self._draft_params, self._draft_state, *chunk_args,
                        dsub, final_arg,
                    )
                    self._attn_dispatch[self._draft_attn_path] += 1
            task.pos += n
            budget -= n
            self._prefill_chunks += 1
            self._prefill_tokens_computed += n
            if task.req.trace is not None:
                task.req.trace.prefill_chunks += 1
                task.req.trace.prefill_tokens += n
            progressed = True
            if final:
                task.first = first
                task.finalized = True
                # Prefill role: requests with decode budget left never go
                # live here — they divert to the handoff queue and decode
                # on the other worker. One-token requests complete
                # locally (their budget is spent by the sampled first
                # token; shipping KV that nothing will decode from is
                # pure transfer waste).
                handoff = (self.role == "prefill"
                           and task.req.max_new_tokens > 1)
                with self._lock:
                    # Publish the prompt's full blocks NOW (dispatch
                    # order guarantees the writes precede any later
                    # matcher's gather), so a burst of shared-prefix
                    # requests hits from the second admission on.
                    self._alloc.insert_full(
                        task.req.tokens, task.table,
                        namespace=(task.req.adapter or "").encode(),
                    )
                    if task.req.max_new_tokens > 1 and not handoff:
                        if task.slot in ending:
                            # The slot is its last request's until the
                            # chunk in flight is settled: wait for that
                            # (and offer the slot to nobody else).
                            ending.remove(task.slot)
                            in_flight.heirs.append(task)
                        else:
                            self._go_live(task)
                    # One-token requests never go live: their budget is
                    # spent by the first token. The reader thread
                    # completes them (and releases their blocks); they
                    # stay in _admitting until then so capacity
                    # accounting and _flush_all keep seeing them.
                self._tasks.remove(task)
                if handoff:
                    # Gather the finished blocks NOW, on the loop thread:
                    # later chunk dispatches donate self.state, so a
                    # reference held by the sender thread could point at
                    # deleted buffers. The gathered copies are
                    # donation-free; the sender only reads them back.
                    # The request stays in _admitting (capacity +
                    # _flush_all) until the handoff resolves.
                    task.kv_payload = self._gather_task_blocks(task)
                    self._handoff_q.put(task)
                else:
                    self._pending_activation.append(task)
                    self._deliver_q.put(task)
        return progressed, offered - budget

    def _go_live(self, task: _PrefillTask) -> None:
        """Host side of a finalized prefill's activation (caller holds
        _lock): from here the slot is the request's to decode in."""
        slot, req = task.slot, task.req
        self._live[slot] = req
        self._admitting.remove(req)
        self._lengths_host[slot] = len(req.tokens)
        self._slot_tables[slot] = task.table
        self._slot_t0[slot] = task.t_pop
        # Fresh request: restart its draft-length adaptation from the
        # cautious midpoint.
        self._slot_k[slot] = self._spec_init_k
        self._accept_ewma[slot] = None

    def _admit_in_shadow(self) -> None:
        """Admission while the decode chunk just dispatched runs: the
        host builds and launches the cycle's prefill chunks where it
        would otherwise only block in `device_get`; they queue on the
        device behind the chunk, so it is not drained while they are
        built. Spends the cycle's budget first; the boundary after
        the settlement gets what is left."""
        self._clock.mark("admit")
        with self._clock.child("shadow"):
            _, self._shadow_spent = self._advance_prefills(
                self.prefill_chunk_tokens
            )

    def _in_shadow(self, chunk: "_Chunk") -> None:
        """The host's work behind the launch of `chunk`, none of which
        the device waits for: the last chunk's tokens to their consumers
        (whose threads wake here, not between a sync and a launch), the
        cycle's admission and prefill chunks, then what the NEXT launch
        would otherwise do with the device drained: its key, and table
        rows for what it can write at most."""
        self._deliver()
        self._admit_in_shadow()
        self._clock.mark("grow")
        self._grow_ahead(chunk)
        self._split_ahead()

    def _split_ahead(self) -> None:
        """The next launch's key(s), now: whatever that launch is, it
        takes the next split of the chain, as it did when it split at
        the launch, so sampled streams are what they were."""
        if self._key_ahead is None:
            self._rng, self._key_ahead = jax.random.split(self._rng)
        if self._spec and self._draft_key_ahead is None:
            self._rng_draft, self._draft_key_ahead = jax.random.split(
                self._rng_draft)

    def _next_key(self):
        """The key of the launch about to be made: one split of the
        chain a launch, whatever its kind, split ahead in the last
        launch's shadow where there was one."""
        sub, self._key_ahead = self._key_ahead, None
        if sub is None:
            self._rng, sub = jax.random.split(self._rng)
        return sub

    def _next_draft_key(self):
        """`_next_key` of the drafter's chain."""
        sub, self._draft_key_ahead = self._draft_key_ahead, None
        if sub is None:
            self._rng_draft, sub = jax.random.split(self._rng_draft)
        return sub

    def _deliver_loop(self) -> None:
        """Reader thread: blocks on each finalized prefill's first-token
        readback and delivers it the instant it lands — decoupled from
        the main loop, which may still be waiting out a decode chunk.
        Also completes one-token requests end-to-end."""
        while True:
            task = self._deliver_q.get()
            if task is None:
                return
            req = task.req
            try:
                first = int(task.first)  # blocks until prefill readback
            except Exception:
                # Poisoned by an engine failure/close mid-flight: the
                # loop's own sync fails too and _flush_all answers the
                # consumer; just unblock any waiter.
                task.delivered.set()
                continue
            now = time.monotonic()
            with self._lock:
                dead = req.out in self._cancelled
                if not dead:
                    req.out.put(first)
                    if req.trace is not None and req.max_new_tokens > 1:
                        # Prefill ends at first delivery; the decode
                        # phase runs to the last token (prefill-role
                        # handoffs never pass through here).
                        req.trace.mark("decode", now)
                self._ttft_s = self._ewma_seed(self._ttft_s, now - req.t_submit)
                self._prefill_s = self._ewma_seed(self._prefill_s, now - task.t_pop)
                self._n_admitted += 1
                self._sum_ttft += now - req.t_submit
                self._sum_prefill += now - task.t_pop
                self._observe_ttft(now - req.t_submit)
                if not self._first_token_emitted:
                    self._first_token_emitted = True
                    # Serving cold-start boundary: submit -> first_token is
                    # the serving analogue of the trainer's first_step.
                    auto_stage("first_token")
                if req.max_new_tokens <= 1:
                    # Budget spent by the first token: complete here.
                    self._cancelled.discard(req.out)
                    self._inflight.discard(req.out)
                    if req in self._admitting:
                        self._admitting.remove(req)
                    for b in task.table:
                        self._alloc.release(b)
                    task.table.clear()
                    self._release_adapter(req.out)
                    self.recorder.finish(
                        req.trace, "cancelled" if dead else "ok", now
                    )
                    req.out.put(None)
                elif dead:
                    # Cancelled between finalize and delivery: the loop's
                    # cancel branch frees the live slot at the next
                    # boundary; nothing to deliver.
                    pass
            task.delivered.set()

    def _wait_activations(self, tasks: List[_PrefillTask]) -> None:
        """Order barrier: before fanning out a decode chunk's tokens,
        make sure the first token of every prefill finalized before the
        chunk's dispatch (`tasks`) has been delivered (the reader thread
        normally finished long ago — its readback completed before the
        decode chunk did). Finals launched in the chunk's shadow belong
        to the next chunk's barrier: waiting for them here would hold
        the host until the prefill chunk ends."""
        for task in tasks:
            task.delivered.wait(timeout=60)
        tasks.clear()

    # -- prefill/decode disaggregation ----------------------------------------

    def _gather_blocks_fn(self, n_pad: int):
        """Jitted per-block gather out of a pool: (L, NB, bs, KV, hd) x
        (n_pad,) ids -> (L, n_pad, bs, KV, hd). One compile per pow-2
        bucket; pad ids carry the out-of-range sentinel (mode="clip"
        duplicates the last block — sliced off host-side). Output is
        replicated (the payload leaves the mesh through the host)."""
        fn = self._gather_fns.get(n_pad)
        if fn is None:
            kw: Dict[str, Any] = {}
            if self._shardings is not None:
                kw = dict(
                    in_shardings=(self._shardings.pool,
                                  self._shardings.replicated),
                    out_shardings=self._shardings.replicated,
                )
            fn = jax.jit(
                lambda pool, ids: jnp.take(pool, ids, axis=1, mode="clip"),
                **kw,
            )
            self._gather_fns[n_pad] = fn
        return fn

    def _gather_task_blocks(self, task: _PrefillTask) -> Dict[str, Any]:
        """Dispatch (async) gathers of a finalized task's blocks from the
        target pool — and the drafter pool when speculation is on, so the
        decode worker's drafter starts from real KV instead of zeros."""
        n = len(task.table)
        n_pad = 1 << max(0, (n - 1).bit_length())
        ids = jnp.asarray(
            task.table + [self._num_blocks] * (n_pad - n), jnp.int32
        )
        fn = self._gather_blocks_fn(n_pad)
        payload: Dict[str, Any] = {
            "n": n,
            "k": fn(self.state.k, ids),
            "v": fn(self.state.v, ids),
        }
        if self._spec:
            payload["draft_k"] = fn(self._draft_state.k, ids)
            payload["draft_v"] = fn(self._draft_state.v, ids)
        return payload

    def _handoff_loop(self) -> None:
        """Prefill-role sender thread: ships each finalized task's KV
        payload to the decode side, then releases its blocks. Decoupled
        from the loop thread so transfer latency (network + readback)
        never stalls the next admission boundary."""
        while True:
            task = self._handoff_q.get()
            if task is None:
                return
            try:
                self._do_handoff(task)
            except BaseException:
                import logging

                logging.getLogger(__name__).exception("kv handoff failed")
                task.delivered.set()

    def _do_handoff(self, task: _PrefillTask) -> None:
        req = task.req

        def _finish(result: object) -> None:
            # Handoff resolved (shipped, cancelled, or failed): the
            # prefill side's claim on the blocks ends here either way —
            # zero residue is the invariant the disagg drills pin.
            with self._lock:
                for b in task.table:
                    self._alloc.release(b)
                task.table.clear()
                self._cancelled.discard(req.out)
                self._inflight.discard(req.out)
                if req in self._admitting:
                    self._admitting.remove(req)
                self._release_adapter(req.out)
            req.out.put(result)
            task.delivered.set()

        if self._stop or self._failed is not None:
            task.delivered.set()  # _flush_all answers the consumer
            return
        try:
            first = int(task.first)  # blocks until the final chunk lands
        except Exception:
            # Poisoned by an engine failure mid-flight: the loop's own
            # sync fails too and _flush_all answers the consumer.
            task.delivered.set()
            return
        with self._lock:
            dead = req.out in self._cancelled
        if dead:
            # Cancel mid-handoff: release everything, ship nothing.
            self.recorder.finish(req.trace, "cancelled")
            _finish(None)
            return
        pay = task.kv_payload
        n = pay["n"]
        t0 = time.monotonic()
        if req.trace is not None:
            req.trace.mark("kv_ship", t0)  # prefill closes here
        try:
            k_np = np.asarray(jax.device_get(pay["k"]))[:, :n]
            v_np = np.asarray(jax.device_get(pay["v"]))[:, :n]
            dk = dv = None
            if "draft_k" in pay:
                dk = np.asarray(jax.device_get(pay["draft_k"]))[:, :n]
                dv = np.asarray(jax.device_get(pay["draft_v"]))[:, :n]
            if req.request_id is not None:
                rid = req.request_id
            else:
                with self._lock:
                    self._handoff_seq += 1
                    rid = self._handoff_seq
            h = KVHandoff(
                request_id=rid,
                epoch=0,  # the transfer client stamps the live epoch
                prompt=list(req.tokens),
                first_token=first,
                max_new_tokens=req.max_new_tokens,
                temperature=req.temperature,
                top_p=req.top_p,
                k=k_np, v=v_np, draft_k=dk, draft_v=dv,
                traceparent=req.traceparent,
            )
            self._kv_transfer.send(h)
        except Exception as e:
            # Transfer failed (decode side gone, epoch churn with
            # retry_stale off): fail THIS request loudly — the consumer
            # must not mistake "prefilled but never decoded" for a
            # complete empty generation.
            self.recorder.finish(req.trace, "error")
            _finish(e)
            return
        dt = time.monotonic() - t0
        now = time.monotonic()
        with self._lock:
            self._handoffs_sent += 1
            self._kv_transfer_bytes += h.payload_bytes
            self._kv_transfer_hist.observe(dt)
            # Prefill-role TTFT: submit -> handoff acked (the token was
            # sampled here; "first token is safely owned downstream" is
            # this worker's responsibility boundary).
            self._ttft_s = self._ewma_seed(self._ttft_s, now - req.t_submit)
            self._n_admitted += 1
            self._sum_ttft += now - req.t_submit
            self._observe_ttft(now - req.t_submit)
        if req.trace is not None:
            req.trace.kv_payload_bytes += h.payload_bytes
            self.recorder.finish(req.trace, "ok", now)
        # Consumer protocol on the prefill worker: no tokens, just the
        # clean end — the DECODE worker streams tokens to ITS consumers.
        _finish(None)

    def submit_prefilled(self, handoff: KVHandoff) -> "queue.Queue[object]":
        """Decode-role admission: accept a prefill worker's finished KV
        blocks + metadata; returns the token stream queue (same protocol
        as submit(), first token delivered from the handoff header).

        Epoch-fenced: a payload stamped with anything other than the
        engine's current `handoff_epoch` raises StaleEpochError (the
        transfer server turns that into a reject reply carrying the
        current epoch) — after bump_handoff_epoch() the old generation's
        payloads must never be absorbed into the fresh pool state.

        Thread-safe (called from transfer-server connection threads):
        only queues; the loop thread allocates blocks and injects."""
        if self.role != "decode":
            raise RuntimeError(
                f"submit_prefilled requires role='decode', engine has"
                f" role={self.role!r}"
            )
        prompt = list(handoff.prompt)
        if not prompt:
            raise ValueError("empty handoff prompt")
        if handoff.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {handoff.max_new_tokens}"
            )
        if len(prompt) + handoff.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens"
                f" {handoff.max_new_tokens} must not exceed max_len"
                f" {self.max_len}"
            )
        c = self.config
        want_k, want_v = (
            (c.n_layers, self._block_size) + row for row in c.kv_row_shapes()
        )
        got_k = (handoff.k.shape[0],) + tuple(handoff.k.shape[2:])
        got_v = (handoff.v.shape[0],) + tuple(handoff.v.shape[2:])
        if (got_k, got_v) != (want_k, want_v) \
                or handoff.k.shape[1] != handoff.v.shape[1]:
            raise ValueError(
                f"handoff KV geometry {handoff.k.shape} does not match"
                f" this engine's pool (L, n, bs, KV, hd) ="
                f" ({c.n_layers}, n, {self._block_size}, {want_k[2]},"
                f" {want_k[3]})"
            )
        expected = (len(prompt) - 1) // self._block_size + 1
        if handoff.n_blocks != expected:
            raise ValueError(
                f"handoff carries {handoff.n_blocks} blocks but the"
                f" prompt needs {expected}"
            )
        out: "queue.Queue[object]" = queue.Queue()
        with self._lock:
            if self._failed is not None:
                raise RuntimeError(f"serving engine failed: {self._failed}")
            if self._stop:
                raise RuntimeError("serving engine is closed")
            if handoff.epoch != self.handoff_epoch:
                self._handoff_stale_rejected += 1
                raise StaleEpochError(handoff.epoch, self.handoff_epoch)
            t_recv = time.monotonic()
            # Decode-side leg of the request's trace: the handoff frame
            # carries the traceparent minted at ingress, so this trace
            # shares the prefill worker's trace_id across processes.
            rec = None
            if self.recorder.enabled:
                rec = self.recorder.begin(
                    handoff.request_id, traceparent=handoff.traceparent,
                    first_phase="queue_wait", t0=t_recv,
                )
            self._prefilled_pending.append((handoff, out, t_recv, rec))
            self._inflight.add(out)
        self._wake.set()
        return out

    def bump_handoff_epoch(self) -> int:
        """Start a new handoff generation (decode role): payloads stamped
        before the bump are rejected on arrival. Call whenever pool state
        is reset out from under in-flight prefills; a co-located
        kv_transfer.TransferServer must bump in lockstep (it announces
        the epoch in its hello)."""
        with self._lock:
            self.handoff_epoch += 1
            return self.handoff_epoch

    def _inject_blocks_fn(self, n_pad: int, draft: bool):
        """Jitted scatter of a handoff payload into a pool: pad ids
        carry the out-of-range sentinel and mode="drop" discards their
        rows. Donates the pool (in-place update); payload arrives
        replicated and lands under the pool's sharding."""
        key = (n_pad, draft)
        fn = self._inject_fns.get(key)
        if fn is None:
            sh = self._draft_shardings if draft else self._shardings
            kw: Dict[str, Any] = {}
            if sh is not None:
                kw = dict(
                    in_shardings=(sh.pool, sh.replicated, sh.replicated),
                    out_shardings=sh.pool,
                )
            fn = jax.jit(
                lambda pool, ids, payload: pool.at[:, ids].set(
                    payload, mode="drop"
                ),
                donate_argnums=0, **kw,
            )
            self._inject_fns[key] = fn
        return fn

    def _pad_payload(self, arr: np.ndarray, n_pad: int) -> np.ndarray:
        if arr.shape[1] == n_pad:
            return arr
        pad = np.zeros(
            (arr.shape[0], n_pad - arr.shape[1]) + arr.shape[2:], arr.dtype
        )
        return np.concatenate([arr, pad], axis=1)

    def _inject_handoff(self, h: KVHandoff, table: List[int]) -> None:
        n = len(table)
        n_pad = 1 << max(0, (n - 1).bit_length())
        ids = jnp.asarray(
            table + [self._num_blocks] * (n_pad - n), jnp.int32
        )
        fn = self._inject_blocks_fn(n_pad, draft=False)
        self.state = self.state._replace(
            k=fn(self.state.k, ids, self._pad_payload(h.k, n_pad)),
            v=fn(self.state.v, ids, self._pad_payload(h.v, n_pad)),
        )
        if self._spec and h.draft_k is not None:
            dfn = self._inject_blocks_fn(n_pad, draft=True)
            self._draft_state = self._draft_state._replace(
                k=dfn(self._draft_state.k, ids,
                      self._pad_payload(h.draft_k, n_pad)),
                v=dfn(self._draft_state.v, ids,
                      self._pad_payload(h.draft_v, n_pad)),
            )
        # Spec on but no drafter payload (the prefill worker ran spec
        # off): the drafter decodes from zero KV for this slot — verify
        # stays exact (correctness never depends on the drafter), the
        # acceptance EWMA just sinks and fallback bounds the perf loss.

    def _place_slot(self, slot: int, table: List[int], length: int,
                    last_token: int, remaining: int, temperature: float,
                    top_p: float, adapter_ix: int) -> None:
        """Device half of placing externally-prepared KV into a slot:
        the state update the final prefill chunk would have applied had
        it run here — table row, cache length, next token to feed, the
        remaining decode budget, sampling params, adapter identity.
        Shared by handoff admission (_activate_prefilled) and swapped-
        slot readmission (_readmit_swapped), so a resumed request steps
        through exactly the state an uninterrupted run would hold."""
        fn = self._place_slot_fn
        if fn is None:
            def _place(state, slot, row, length, last, budget, temp,
                       top_p, aix):
                sel = (jnp.arange(state.lengths.shape[0], dtype=jnp.int32)
                       == slot)
                return state._replace(
                    block_tables=state.block_tables.at[slot].set(row),
                    lengths=jnp.where(sel, length, state.lengths),
                    last_token=jnp.where(sel, last, state.last_token),
                    active=jnp.where(sel, budget > 0, state.active),
                    remaining=jnp.where(sel, budget, state.remaining),
                    temperature=jnp.where(sel, temp, state.temperature),
                    top_p=jnp.where(sel, top_p, state.top_p),
                    adapter_ix=jnp.where(sel, aix, state.adapter_ix),
                )

            kw: Dict[str, Any] = {}
            if self._shardings is not None:
                kw = dict(
                    in_shardings=(self._shardings.state,)
                    + (self._shardings.replicated,) * 8,
                    out_shardings=self._shardings.state,
                )
            fn = jax.jit(_place, donate_argnums=0, **kw)
            self._place_slot_fn = fn
        self.state = fn(
            self.state,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(self._pad_table(table), jnp.int32),
            jnp.asarray(length, jnp.int32),
            jnp.asarray(last_token, jnp.int32),
            jnp.asarray(remaining, jnp.int32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(adapter_ix, jnp.int32),
        )

    def _activate_prefilled(self, slot: int, table: List[int], length: int,
                            first: int, h: KVHandoff) -> None:
        """Handoff flavor of _place_slot: the prefill-sampled first
        token becomes last_token, the budget drops by the token already
        delivered, and adapter identity clears (handoffs never carry it
        — LoRA engines must be role='unified')."""
        self._place_slot(
            slot, table, length, first, h.max_new_tokens - 1,
            h.temperature, h.top_p, -1,
        )

    def _admit_prefilled(self) -> bool:
        """Decode-role admission boundary (loop thread): drain queued
        handoffs in arrival order into free slots — fresh blocks from
        THIS pool's allocator, payload scattered in, prompt published to
        the prefix cache, slot activated on device, first token (sampled
        by the prefill worker) delivered immediately. A starved
        allocation leaves the handoff queued and retries next boundary;
        refcounts stay coherent through the same release paths as local
        requests."""
        progressed = False
        while True:
            with self._lock:
                if not self._prefilled_pending:
                    return progressed
                h, out, t_recv, rec = self._prefilled_pending[0]
                dead = out in self._cancelled
                if dead:
                    self._prefilled_pending.pop(0)
                    self._cancelled.discard(out)
                    self._inflight.discard(out)
            if dead:
                self.recorder.finish(rec, "cancelled")
                out.put(None)
                progressed = True
                continue
            busy = {t.slot for t in self._tasks}
            live_n = sum(r is not None for r in self._live)
            free = [s for s in range(self.slots)
                    if self._live[s] is None and s not in busy]
            if not free or live_n + len(busy) >= self._max_resident:
                return progressed
            n = h.n_blocks
            with self._lock:
                table: List[int] = []
                for _ in range(n):
                    b = self._alloc.alloc()
                    if b is None:
                        break
                    table.append(b)
                if len(table) < n:
                    for b in table:
                        self._alloc.release(b)
                    return progressed  # pool starved: retry next boundary
                self._prefilled_pending.pop(0)
            if rec is not None:
                rec.mark("kv_adopt")  # queue_wait closes here
            self._inject_handoff(h, table)
            prompt = list(h.prompt)
            first = int(h.first_token)
            slot = free[0]
            req = _Request(prompt, h.max_new_tokens, out,
                           float(h.temperature), float(h.top_p), t_recv,
                           h.request_id, None, -1, h.traceparent, rec)
            with self._lock:
                self._alloc.insert_full(prompt, table)
                self._handoffs_received += 1
                self._kv_transfer_bytes += h.payload_bytes
                if rec is not None:
                    rec.kv_payload_bytes += h.payload_bytes
                if h.max_new_tokens > 1:
                    self._live[slot] = req
                    self._lengths_host[slot] = len(prompt)
                    self._slot_tables[slot] = table
                    self._slot_k[slot] = self._spec_init_k
                    self._accept_ewma[slot] = None
                    self._slot_t0[slot] = t_recv
                else:
                    # Defensive: the prefill role completes one-token
                    # requests locally, but a direct submit_prefilled
                    # caller may not — budget spent by the first token.
                    for b in table:
                        self._alloc.release(b)
                    self._inflight.discard(out)
            if h.max_new_tokens > 1:
                self._activate_prefilled(slot, table, len(prompt), first, h)
            now = time.monotonic()
            with self._lock:
                still_wanted = out not in self._cancelled
                if still_wanted:
                    out.put(first)
                    if rec is not None:
                        if h.max_new_tokens > 1:
                            rec.mark("decode", now)  # kv_adopt closes here
                        else:
                            self.recorder.finish(rec, "ok", now)
                    if h.max_new_tokens <= 1:
                        out.put(None)
                elif h.max_new_tokens <= 1:
                    # Cancelled inside the admission window: blocks were
                    # already released above; answer the consumer here
                    # (a live slot instead gets the fan-out cancel path).
                    self._cancelled.discard(out)
                    self.recorder.finish(rec, "cancelled", now)
                    out.put(None)
                # Decode-role TTFT: handoff receipt -> first delivery
                # (admission wait + injection; the submit->handoff leg is
                # the prefill worker's TTFT).
                self._ttft_s = self._ewma_seed(self._ttft_s, now - t_recv)
                self._n_admitted += 1
                self._sum_ttft += now - t_recv
                self._observe_ttft(now - t_recv)
                if not self._first_token_emitted:
                    self._first_token_emitted = True
                    auto_stage("first_token")
            progressed = True

    # -- hierarchical KV: host tier + slot preemption -------------------------

    def _weight(self, req: _Request) -> float:
        """QoS weight for preemption decisions — the same weights map
        the dataplane DRR scheduler uses (unknown tenants weigh 1.0)."""
        return float(self._qos_weights.get(req.tenant, 1.0))

    def _gather_chain(self, table: List[int]) -> Dict[str, np.ndarray]:
        """Device->host ship of a block chain: gathered per block out
        of the pool(s) and read back as numpy — the same array frames
        kv_transfer puts on the socket, minus the socket."""
        n = len(table)
        n_pad = 1 << max(0, (n - 1).bit_length())
        ids = jnp.asarray(
            table + [self._num_blocks] * (n_pad - n), jnp.int32
        )
        fn = self._gather_blocks_fn(n_pad)
        out = {
            "k": np.asarray(jax.device_get(fn(self.state.k, ids)))[:, :n],
            "v": np.asarray(jax.device_get(fn(self.state.v, ids)))[:, :n],
        }
        if self._spec:
            out["draft_k"] = np.asarray(
                jax.device_get(fn(self._draft_state.k, ids))
            )[:, :n]
            out["draft_v"] = np.asarray(
                jax.device_get(fn(self._draft_state.v, ids))
            )[:, :n]
        return out

    def _inject_chain(self, arrays: Dict[str, np.ndarray],
                      table: List[int]) -> None:
        """Host->device ship: scatter a gathered chain into freshly
        allocated blocks (byte-lossless inverse of _gather_chain)."""
        n = len(table)
        n_pad = 1 << max(0, (n - 1).bit_length())
        ids = jnp.asarray(
            table + [self._num_blocks] * (n_pad - n), jnp.int32
        )
        fn = self._inject_blocks_fn(n_pad, draft=False)
        self.state = self.state._replace(
            k=fn(self.state.k, ids, self._pad_payload(arrays["k"], n_pad)),
            v=fn(self.state.v, ids, self._pad_payload(arrays["v"], n_pad)),
        )
        if self._spec and "draft_k" in arrays:
            dfn = self._inject_blocks_fn(n_pad, draft=True)
            self._draft_state = self._draft_state._replace(
                k=dfn(self._draft_state.k, ids,
                      self._pad_payload(arrays["draft_k"], n_pad)),
                v=dfn(self._draft_state.v, ids,
                      self._pad_payload(arrays["draft_v"], n_pad)),
            )

    def _spill_block(self, key: tuple, b: int) -> None:
        """BlockAllocator eviction hook (loop thread): ship the victim
        block's KV to the host tier before the block recycles, keyed by
        its prefix-chain key so match() can resurrect it. A payload the
        budget can't hold is dropped — the block then just dies, as it
        did before the tier existed."""
        arrays = self._gather_chain([b])
        self._host_tier.put(key, list(arrays.items()))

    def _swap_in_block(self, key: tuple) -> Optional[int]:
        """BlockAllocator miss hook: resurrect a spilled block from the
        host tier into a fresh device block. The alloc may itself evict
        and spill an LRU victim (depth-one reentry; a spill never
        allocates). None when the key isn't spilled or no device block
        frees up — the payload then stays host-side for a later probe
        instead of being lost."""
        tier = self._host_tier
        payload = tier.get(key)
        if payload is None:
            return None
        t0 = time.monotonic()
        b = self._alloc.alloc()
        if b is None:
            return None
        self._inject_chain(payload, [b])
        tier.pop(key)
        self._swap_in_hist.observe(time.monotonic() - t0)
        return b

    def _preempt_slot(self, slot: int) -> bool:
        """Swap a live slot's whole block chain out to the host tier
        (loop thread, chunk boundary): KV + sampling scalars park
        host-side, the slot and its device blocks free immediately, and
        readmission resumes the request bit-exact at temperature 0. The
        adapter ref is NOT released — it must survive the swap. False
        (the slot keeps decoding) when the host budget can't pin the
        payload even after evicting every spilled block."""
        req = self._live[slot]
        table = self._slot_tables[slot]
        if req is None or table is None or self._host_tier is None:
            return False
        t0 = time.monotonic()
        if req.trace is not None:
            req.trace.mark("kv_swap_out", t0)  # decode closes here
        # Scalars from DEVICE state, not the host mirrors: resume must
        # restart from exactly the boundary state the decode program
        # left behind.
        length, last, rem = (
            int(x) for x in jax.device_get((
                self.state.lengths[slot],
                self.state.last_token[slot],
                self.state.remaining[slot],
            ))
        )
        # Only the filled chain ships; lookahead blocks past `length`
        # hold no KV yet and re-grow after readmission.
        n_keep = (length - 1) // self._block_size + 1
        arrays = self._gather_chain(table[:n_keep])
        nbytes = sum(a.nbytes for a in arrays.values())
        if not self._host_tier.reserve(nbytes):
            if req.trace is not None:
                req.trace.mark("decode")  # denied: keep decoding
            return False
        sw = _SwappedSlot(req, length, last, rem, arrays, nbytes,
                          time.monotonic(), self._slot_t0[slot])
        with self._lock:
            self._live[slot] = None
            self._release_slot_blocks(slot, cache_tail=False)
            self._swapped.append(sw)
            self._preempt_requests.discard(req.out)
        self.state = self._retire(slot)
        self._preemptions += 1
        if req.trace is not None:
            req.trace.mark("queue_wait")  # kv_swap_out closes here
        return True

    def _try_queue_jump(self, req: _Request) -> bool:
        """QoS preemption at admission: when every slot is busy, a
        pending request whose tenant weight STRICTLY exceeds the
        lightest live request's swaps that victim out mid-generation
        instead of waiting for a natural retire. Ties go to the
        resident (no churn between equals); among equal-weight victims
        the longest-resident one is taken."""
        if self._host_tier is None or not self._qos_weights:
            return False
        if self._chunk is not None:
            # A decode chunk is in flight: its slots' lengths and tokens
            # are not the host's yet. The request parks; the boundary
            # after its settlement asks again.
            return False
        w = self._weight(req)
        victim: Optional[int] = None
        vw = 0.0
        for slot, r in enumerate(self._live):
            if r is None:
                continue
            rw = self._weight(r)
            if (victim is None or rw < vw
                    or (rw == vw
                        and self._slot_t0[slot] < self._slot_t0[victim])):
                victim, vw = slot, rw
        if victim is None or not (w > vw):
            return False
        return self._preempt_slot(victim)

    def _process_preempt_requests(self) -> None:
        """Boundary service of preempt() asks: swap out any live slot
        whose consumer requested it. Asks for requests no longer in
        flight are dropped; asks for requests not yet live persist
        until they are (or terminate)."""
        with self._lock:
            self._preempt_requests &= self._inflight
            wanted = set(self._preempt_requests)
        if not wanted:
            return
        for slot, req in enumerate(self._live):
            if req is not None and req.out in wanted:
                self._preempt_slot(slot)

    def _readmit_swapped(self) -> bool:
        """Admission boundary for swapped-out requests: heaviest tenant
        first (FIFO within a weight class), each into a free slot +
        fresh device blocks — allocation may itself evict+spill LRU
        cache blocks, which is the point. Entries stay parked (and
        retry next boundary) while slots, residency headroom, or device
        blocks are short."""
        progressed = False
        while True:
            with self._lock:
                # Cancelled while parked: answer + unpin, no device work.
                keep = []
                for sw in self._swapped:
                    if sw.req.out in self._cancelled:
                        self._cancelled.discard(sw.req.out)
                        self._inflight.discard(sw.req.out)
                        self._release_adapter(sw.req.out)
                        self._host_tier.unreserve(sw.nbytes)
                        self.recorder.finish(sw.req.trace, "cancelled")
                        sw.req.out.put(None)
                        progressed = True
                    else:
                        keep.append(sw)
                self._swapped[:] = keep
                if not self._swapped:
                    return progressed
                busy = {t.slot for t in self._tasks}
                live_n = sum(r is not None for r in self._live)
                free = [s for s in range(self.slots)
                        if self._live[s] is None and s not in busy]
                if not free or live_n + len(busy) >= self._max_resident:
                    return progressed
                pick = min(
                    range(len(self._swapped)),
                    key=lambda i: (-self._weight(self._swapped[i].req), i),
                )
                sw = self._swapped[pick]
                n = int(sw.arrays["k"].shape[1])
                table: List[int] = []
                for _ in range(n):
                    b = self._alloc.alloc()
                    if b is None:
                        break
                    table.append(b)
                if len(table) < n:
                    for b in table:
                        self._alloc.release(b)
                    return progressed  # pool starved; retry next boundary
                self._swapped.pop(pick)
            slot = free[0]
            t0 = time.monotonic()
            if sw.req.trace is not None:
                sw.req.trace.mark("kv_swap_in", t0)  # queue_wait closes
            self._inject_chain(sw.arrays, table)
            self._place_slot(slot, table, sw.length, sw.last_token,
                             sw.remaining, sw.req.temperature,
                             sw.req.top_p, sw.req.adapter_ix)
            with self._lock:
                self._live[slot] = sw.req
                self._lengths_host[slot] = sw.length
                self._slot_tables[slot] = table
                self._slot_k[slot] = self._spec_init_k
                self._accept_ewma[slot] = None
                self._slot_t0[slot] = sw.t0
                self._host_tier.unreserve(sw.nbytes)
            self._slot_swap_ins += 1
            self._swap_in_hist.observe(time.monotonic() - t0)
            if sw.req.trace is not None:
                sw.req.trace.mark("decode")  # kv_swap_in closes here
            progressed = True

    # -- decode ---------------------------------------------------------------

    def _ensure_decode_blocks(self, lookahead: Optional[int] = None) -> None:
        """Grow live slots' tables to cover the next chunk's writes —
        `lookahead` rows past each slot's length (default: the decode
        chunk's steps_per_sync; a speculation round passes k+1, its
        draft/verify write window). A slot the pool cannot feed
        (undersized kv_pool_blocks under concurrent worst-case load) is
        force-retired with an error — silently dropping its KV writes
        would corrupt the stream."""
        if lookahead is None:
            lookahead = self._steps_per_sync
        bs = self._block_size
        updates: Dict[int, List[int]] = {}
        for slot in range(self.slots):
            table = self._slot_tables[slot]
            if self._live[slot] is None or table is None:
                continue
            need = min(
                (self._lengths_host[slot] + lookahead - 1) // bs + 1,
                self._max_blocks,
            )
            grew = False
            starved = False
            while len(table) < need:
                with self._lock:
                    b = self._alloc.alloc()
                if b is None:
                    starved = True
                    break
                table.append(b)
                grew = True
            if starved:
                # With a host tier the starved slot parks instead of
                # dying: its chain swaps out, freeing its blocks for
                # the slots that stay resident, and it readmits when
                # pressure clears — the overcommit path. Without one
                # (or when the host budget is full) the old contract
                # stands: fail loudly, never drop KV writes.
                if self._host_tier is not None and self._preempt_slot(slot):
                    continue
                self._force_retire(
                    slot,
                    RuntimeError(
                        "kv block pool exhausted mid-decode"
                        " (raise kv_pool_blocks)"
                    ),
                )
                continue
            if grew:
                updates[slot] = self._pad_table(table)
        self._push_table_rows(updates)

    def _grow_ahead(self, chunk: "_Chunk") -> None:
        """In `chunk`'s shadow, before its emissions are known: table
        rows for what the NEXT chunk can write at most (a slot in `chunk`
        may be a whole chunk further on by then, and no request writes
        past its budget), from blocks the pool gives. The boundary's
        `_ensure_decode_blocks` then usually finds nothing to do; it
        alone decides what happens to a slot the pool cannot feed."""
        bs = self._block_size
        updates: Dict[int, List[int]] = {}
        dry = False
        for slot, req in enumerate(self._live):
            table = self._slot_tables[slot]
            if req is None or table is None or dry:
                continue
            rows = self._lengths_host[slot] + chunk.steps * (
                2 if chunk.live[slot] is not None else 1)
            rows = min(rows, len(req.tokens) + req.max_new_tokens - 1)
            need = min((rows - 1) // bs + 1, self._max_blocks)
            had = len(table)
            while len(table) < need and not dry:
                with self._lock:
                    b = self._alloc.alloc()
                if b is None:
                    dry = True  # the boundary's to settle
                else:
                    table.append(b)
            if len(table) > had:
                updates[slot] = self._pad_table(table)
        self._push_table_rows(updates)

    def _push_table_rows(self, updates: Dict[int, List[int]]) -> None:
        """Write grown slots' (padded) table rows into the device state:
        one launch of the one compiled row update a slot."""
        if not updates:
            return
        bt = self.state.block_tables
        for s in sorted(updates):
            bt = self._set_table_row(
                bt,
                jnp.asarray(s, jnp.int32),
                jnp.asarray(updates[s], jnp.int32),
            )
        self.state = self.state._replace(block_tables=bt)

    def _ensure_spec_writable(self, k: int) -> None:
        """Copy-on-write pass over each live slot's speculation write
        window (rows length..length+k): the draft and verify programs
        write those rows directly into pool blocks, so a block still
        shared with the prefix cache or a sharer (a published tail the
        slot decodes into) must be privatized FIRST — rejected-draft
        writes into a refcounted block would corrupt every other
        holder. Under the engine's invariants the window is virtually
        always private already (prefill CoWs the matched tail before
        any write; growth allocates fresh blocks), so this pass is a
        cheap refcount check per window block."""
        bs = self._block_size
        updates: Dict[int, List[int]] = {}
        for slot in range(self.slots):
            table = self._slot_tables[slot]
            if self._live[slot] is None or table is None:
                continue
            first_blk = self._lengths_host[slot] // bs
            last_blk = min(
                (self._lengths_host[slot] + k) // bs, len(table) - 1
            )
            for idx in range(first_blk, last_blk + 1):
                with self._lock:
                    b, needs_copy = self._alloc.ensure_writable(table[idx])
                if b is None:
                    if (self._host_tier is not None
                            and self._preempt_slot(slot)):
                        break
                    self._force_retire(
                        slot,
                        RuntimeError(
                            "kv block pool exhausted during speculative"
                            " copy-on-write (raise kv_pool_blocks)"
                        ),
                    )
                    break
                if needs_copy:
                    src = jnp.asarray(table[idx], jnp.int32)
                    dst = jnp.asarray(b, jnp.int32)
                    self.state = self._copy_block(self.state, src, dst)
                    self._draft_state = self._copy_draft_block(
                        self._draft_state, src, dst
                    )
                    table[idx] = b
                    updates[slot] = self._pad_table(table)
        self._push_table_rows(updates)

    def _force_retire(self, slot: int, error: BaseException) -> None:
        self._deliver()  # the error comes after the tokens it follows
        req = self._live[slot]
        with self._lock:
            self._live[slot] = None
            if req is not None:
                self._cancelled.discard(req.out)
                self._inflight.discard(req.out)
                self.recorder.finish(req.trace, "error")
            self._release_slot_blocks(slot, cache_tail=False)
            if req is not None:
                self._release_adapter(req.out)
        self.state = self._retire(slot)
        if req is not None:
            req.out.put(error)

    def _release_slot_blocks(self, slot: int, cache_tail: bool,
                             prompt: Optional[List[int]] = None,
                             namespace: bytes = b"") -> None:
        """Return a retired slot's blocks to the pool (caller holds
        _lock). With `cache_tail`, first publish the prompt's partial
        tail block for future prefix hits — full blocks were already
        published at finalize. `namespace` keys the tail entry to the
        request's adapter so tenants never share cached KV."""
        table = self._slot_tables[slot]
        if table is None:
            return
        if cache_tail and prompt is not None:
            self._alloc.insert_tail(prompt, table, namespace=namespace)
        for b in table:
            self._alloc.release(b)
        self._slot_tables[slot] = None
        self._lengths_host[slot] = 0

    def _retire(self, slot: int):
        s = self.state
        return s._replace(
            active=s.active.at[slot].set(False),
            remaining=s.remaining.at[slot].set(0),
            adapter_ix=s.adapter_ix.at[slot].set(-1),
        )

    def _ewma(self, prev: float, sample: float, alpha: float = 0.2) -> float:
        return prev + alpha * (sample - prev)

    def _ewma_seed(self, prev: float, sample: float, alpha: float = 0.2) -> float:
        """EWMA whose zero value means "unseeded": the first sample sets
        the gauge directly instead of averaging against the 0 seed."""
        return sample if prev == 0.0 else prev + alpha * (sample - prev)

    # -- loop ----------------------------------------------------------------

    def _loop(self) -> None:
        """The engine thread, software-pipelined by one stage at each end
        of a decode chunk: of a cycle's host work only what needs the
        last chunk's outcome stands between its sync and the next
        launch. A cycle with something live launches and settles chunk
        N, and hands out N-1's tokens:

        admit     what must know N-1's outcome, settled at the end of
                  the last cycle (readmit, preempt, adopt: they read
                  `_lengths_host` or move live slots) and the prefill
                  chunks the last shadow left budget for;
        grow      block provisioning for N: what the last shadow could
                  not grow ahead, and what happens to a starved slot;
        dispatch  launch N (async), with the key split ahead;
        barrier   in N's shadow from here to the sync: first tokens of
                  the slots in N-1, then
        fan_out   N-1's tokens and clean ends to their consumers (child
                  `shadow`: their threads wake behind a launch);
        admit     admit requests into free slots and into slots whose
                  budget N exhausts, build and launch the cycle's
                  prefill chunks, which queue on the device behind N;
        grow      ahead, for N+1: table rows for what it can write at
                  most, and its key;
        sync      read N back;
        fan_out   settle N: lengths, cancelled and ended slots freed
                  (before their last tokens are handed out), a prefill
                  finalized into a slot N ended goes live. If nothing
                  is left live no launch will follow, and N's tokens go
                  out here (barrier, fan_out with no child).

        The device sees decode N, chunk(s), decode N+1 with no drain
        wherever a chunk rides, and where none does it waits for the
        sync's wake-up, the settlement and one launch. With nothing
        live, admission runs alone. A speculation round is a chunk
        (`_spec_round`); LoRA banks, the host tier's readmit / preempt,
        the decode role's adoption and state-space slots ride the same
        order: they act at the boundary, on settled slots."""
        clock = self._clock
        while not self._stop:
            try:
                live = sum(r is not None for r in self._live)
                if (not live and not self._tasks
                        and not self._pending_activation):
                    with self._lock:
                        queued_handoffs = bool(self._prefilled_pending)
                        waiting = (bool(self._swapped)
                                   or self._next_req is not None)
                    if (self._pending.empty() and not queued_handoffs
                            and not waiting):
                        clock.mark("wait")
                        self._wake.wait(timeout=0.2)
                        self._wake.clear()
                        continue
                clock.begin("admit", live=live, tasks=len(self._tasks),
                            pending=self._pending.qsize())
                if not live:
                    # Nothing decoding: admission runs alone, with a whole
                    # budget; the next iteration dispatches the first
                    # decode chunk for the freshly activated slots.
                    # Swapped-out requests get first claim on the free
                    # capacity. (A one-token request finalized in the
                    # last chunk's shadow is waited out here too.)
                    self._shadow_spent = 0
                    progressed = self._readmit_swapped()
                    moved, _ = self._advance_prefills(self.prefill_chunk_tokens)
                    progressed |= moved | self._admit_prefilled()
                    clock.mark("barrier")
                    self._wait_activations(self._pending_activation)
                    clock.end()
                    self._admission_barrier_s += clock.cycle_seconds("barrier")
                    if not progressed and (self._tasks or self._swapped):
                        clock.mark("wait")
                        time.sleep(0.001)  # pool starved, nothing live
                    continue
                # 1) The boundary: host and device agree. What the shadow
                #    left of the cycle's prefill budget is spent here, so
                #    a request that arrived (or a slot that freed) while
                #    the last chunk ran waits no longer than it did when
                #    all admission sat here; under load the shadow spent
                #    it all and nothing launches. Block growth runs AFTER
                #    admissions: a prefill that finalizes here goes live
                #    in THIS chunk, and its table so far only covers the
                #    prompt — growing first would let the chunk's writes
                #    past the last prompt block hit the pad sentinel and
                #    silently drop.
                self._readmit_swapped()
                self._process_preempt_requests()
                left = self.prefill_chunk_tokens - self._shadow_spent
                self._shadow_spent = 0
                if left > 0:
                    self._advance_prefills(left)
                self._admit_prefilled()
                clock.mark("grow")
                # 2) Launch the chunk (async), do everything that does not
                #    need its outcome in its shadow, sync on it.
                if self._spec and self._spec_cooldown == 0:
                    chunk = self._spec_round()
                    if chunk is None:
                        self._deliver()
                        clock.end()
                        continue  # every slot force-retired mid-round
                else:
                    chunk = self._decode_chunk()
                # 3) Settle it. Its tokens go out behind the next launch.
                self._settle(chunk)
                if not any(r is not None for r in self._live):
                    self._deliver()
                clock.end()
            except Exception as e:  # device/compile error: fail loudly, not
                # by wedging every consumer on a dead queue.
                clock.close()
                if self._stop:
                    # close() raced the in-flight step (donated buffers /
                    # deleted arrays are expected then); consumers were
                    # already flushed with the close error.
                    return
                with self._lock:
                    self._failed = e
                self._flush_all(e)
                # Surface in logs, not by re-raising into the thread
                # excepthook: the failure is already delivered to every
                # consumer and to future submit() calls via _failed.
                import logging

                logging.getLogger(__name__).exception(
                    "serving engine loop failed"
                )
                return
        clock.close()

    def _decode_chunk(self) -> "_Chunk":
        """One decode chunk of `steps_per_sync` steps, from the cycle's
        `grow` phase to its read-back (phase `fan_out` open)."""
        clock = self._clock
        self._ensure_decode_blocks()
        chunk = self._begin_chunk(self._steps_per_sync, self._steps_per_sync,
                                  clock.mark("dispatch"))
        sub = self._next_key()
        if self._lora is not None and self._lora.inflight > 0:
            self.state, tokens, active, *pairs = self._step(
                self.params, self.state, sub, self._lora.bank
            )
        else:
            self.state, tokens, active, *pairs = self._step_base(
                self.params, self.state, sub
            )
        self._attn_dispatch[self._attn_path] += 1
        self._in_shadow(chunk)
        clock.mark("sync")
        chunk.toks = jax.device_get(tokens)  # (B, steps_per_sync)
        chunk.still = jax.device_get(active)
        if pairs:   # (a share of the expert bank: what the programs counted)
            routed, here = jax.device_get(pairs[0])
            self._moe_pairs += int(routed)
            self._moe_local_pairs += int(here)
        self._observe_chunk_seconds(chunk, clock.mark("fan_out"))
        if self._spec and self._spec_cooldown > 0:
            self._spec_fallback_rounds += 1
            self._spec_cooldown -= 1
            if self._spec_cooldown == 0:
                # Re-probe cautiously: shortest drafts,
                # fresh acceptance estimates.
                self._slot_k = [1] * self.slots
                self._accept_ewma = [None] * self.slots
                self._spec_low_streak = 0
        return chunk

    def _begin_chunk(self, steps: int, sure: int, t_launch: int) -> "_Chunk":
        """A decode chunk (or speculation round) of `steps` steps is
        about to launch: freeze who is in it, and count it. What goes
        live or finalizes from here to its settlement (the shadow) is
        the next chunk's. A slot within `sure` tokens of its budget's end
        (what the launch emits at the least for a live slot) ends in
        this chunk whatever happens (the stop rules only end it sooner),
        so the shadow may admit into it."""
        ending = {
            slot for slot, req in enumerate(self._live)
            if req is not None and req.max_new_tokens - 1 - (
                self._lengths_host[slot] - len(req.tokens)) <= sure
        }
        chunk = self._chunk = _Chunk(
            steps, list(self._live), ending, self._pending_activation,
            t_launch)
        self._pending_activation = []
        self._count_decode_launch(steps)
        return chunk

    def _count_decode_launch(self, steps: int) -> None:
        """One decode chunk (or speculation round) of `steps` steps is
        about to launch over the slots live right now."""
        blocks, dead = self._layer_blocks()
        layer_blocks = blocks * self.config.n_attn_layers
        live = sum(r is not None for r in self._live)
        state_layers = self.config.n_state_layers
        moved = live if self._scan_path == "pallas" else self.slots
        self._decode_state_rows += steps * live * state_layers
        self._decode_state_rows_computed += steps * moved * state_layers
        self._decode_steps += steps
        self._decode_slot_steps += steps * live
        self._decode_live_blocks += steps * blocks
        self._decode_table_columns += steps * self.slots * self._max_blocks
        self._decode_layer_blocks += steps * layer_blocks
        self._decode_attended_blocks += steps * (layer_blocks - dead)
        self._decode_window_dead_blocks += steps * dead
        self._count_expert_slots(steps * live, self.slots, 1, steps)

    def _layer_blocks(self) -> Tuple[int, int]:
        """Over the live slots, from `_lengths_host` (no device sync): the
        blocks their contexts fill (in every layer), and over the WINDOW
        layers too the blocks that lie wholly behind their slot's window —
        no later step reads them, and the one pool keeps them (ROADMAP
        R2). A slot's walk in a window layer starts at column (length -
        window) // block, as the kernel's does
        (paged_attention.first_column), so blocks x layers less the second
        is what a decode step's attention walks visit."""
        c, bs = self.config, self._block_size
        window_layers = sum(c.window(kind) > 0 for kind in c.layer_types)
        blocks = behind = 0
        for slot, r in enumerate(self._live):
            if r is not None:
                n = self._lengths_host[slot]
                blocks += -(-n // bs)
                behind += max(n - c.sliding_window, 0) // bs
        return blocks, behind * window_layers

    @property
    def _whole_bank(self) -> bool:
        """What `moe.moe_mlp` sees in this engine's programs: the expert
        bank whole on the device as plain arrays (no mesh partitions the
        programs, no int8 bank)."""
        bank = self.params["layers"].get("we_gate")
        return self.mesh is None and not isinstance(bank, QTensor)

    def _count_expert_slots(
        self, tokens: int, rows: int, row_len: int, steps: int = 1
    ) -> None:
        """A launch routes `tokens` valid tokens through every expert layer
        and multiplies, in each of its `steps` steps, the slots `moe.plan`
        says its program does for rows x row_len tokens, padding and dead
        rows included: the capacity dispatch's experts x rows x
        capacity(row_len), or the routed path's rows and row tiles (the
        same function `moe.moe_mlp` dispatched on when the program was
        traced). Counts the launch, and whether its expert layers took the
        routed path."""
        c = self.config
        if c.n_experts == 0:
            return
        layers = c.n_layers - c.n_dense_layers
        routed, slots, _ = moe.plan(c, rows, row_len, self._whole_bank)
        if not c.expert_share:   # (a share's pairs: counted on the device)
            self._moe_pairs += layers * tokens * c.experts_per_token
            self._moe_local_pairs += layers * tokens * c.experts_per_token
        self._moe_computed_slots += layers * slots * steps
        self._moe_routed_launches += routed
        self._moe_expert_launches += 1

    def _observe_chunk_seconds(self, chunk: "_Chunk", t_read: int) -> None:
        """Launch-to-readback wall time of the chunk whose `sync` phase
        closed at `t_read`, the host's work in its shadow included (the
        cadence gauges and the TPT series read it)."""
        chunk.seconds = (t_read - chunk.t_launch) / 1e9
        self._chunk_s = self._ewma(self._chunk_s, chunk.seconds)

    def _spec_round(self) -> Optional["_Chunk"]:
        """One speculation boundary: drafter proposes k tokens per
        slot, the target verifies all k+1 positions in one forward, and
        the host adapts per-slot draft lengths from what survived.
        Entered in the cycle's `grow` phase, left in `fan_out` like a
        decode chunk; the shadow runs behind the verify launch. Returns
        the round as a chunk (toks (B, k+1) with -1 padding) so the
        settlement and the delivery are shared, or None when no slot
        survived block provisioning."""
        clock = self._clock
        k_cur = max(
            (self._slot_k[s] for s in range(self.slots)
             if self._live[s] is not None),
            default=self._spec_init_k,
        )
        self._ensure_decode_blocks(k_cur + 1)
        self._ensure_spec_writable(k_cur)
        if not any(r is not None for r in self._live):
            return None
        t_pf = clock.mark("dispatch")
        # A round emits one token at least.
        chunk = self._begin_chunk(k_cur + 1, 1, t_pf)
        dsub = self._next_draft_key()
        vsub = self._next_key()
        dk, dv, drafts, qlogits = self._spec_draft_fn(k_cur)(
            self._draft_params, self._draft_state.k, self._draft_state.v,
            self.state.block_tables, self.state.lengths,
            self.state.last_token, self.state.active,
            self.state.temperature, self.state.top_p, dsub,
        )
        self._draft_state = self._draft_state._replace(k=dk, v=dv)
        drafts.block_until_ready()  # draft/verify timing split
        t_draft = time.monotonic_ns()
        if self._lora is not None and self._lora.inflight > 0:
            self.state, emitted, accepted, active = self._spec_verify_fn(
                k_cur, lora=True
            )(self.params, self.state, drafts, qlogits, vsub,
              self._lora.bank)
        else:
            self.state, emitted, accepted, active = self._spec_verify_fn(
                k_cur
            )(self.params, self.state, drafts, qlogits, vsub)
        self._in_shadow(chunk)
        clock.mark("sync")
        toks = chunk.toks = jax.device_get(emitted)  # (B, k_cur + 1), -1 padded
        chunk.still = jax.device_get(active)
        acc = jax.device_get(accepted)
        t_sync = clock.mark("fan_out")
        self._attn_dispatch[self._draft_attn_path] += 1
        self._attn_dispatch[self._attn_path] += 1
        self._observe_chunk_seconds(chunk, t_sync)
        self._t_spec_draft += (t_draft - t_pf) / 1e9
        self._t_spec_verify += (t_sync - t_draft) / 1e9
        # Acceptance bookkeeping + per-slot draft-length adaptation.
        self._spec_rounds += 1
        live_rates = []
        n_round_tokens = 0
        for slot, req in enumerate(chunk.live):
            if req is None:
                continue
            a = int(acc[slot])
            self._spec_proposed += k_cur
            self._spec_accepted += a
            self._spec_rejected += k_cur - a
            n_round_tokens += int((toks[slot] >= 0).sum())
            tr = req.trace
            if tr is not None:
                tr.spec_rounds += 1
                tr.spec_drafted += k_cur
                tr.spec_accepted += a
                tr.spec_rejected += k_cur - a
            rate = a / k_cur
            prev = self._accept_ewma[slot]
            ewma = rate if prev is None else prev + 0.3 * (rate - prev)
            self._accept_ewma[slot] = ewma
            live_rates.append(ewma)
            if ewma > 0.8 and self._slot_k[slot] < self._spec_max_draft:
                self._slot_k[slot] += 1
            elif ewma < 0.4 and self._slot_k[slot] > 1:
                self._slot_k[slot] -= 1
        if live_rates:
            mean_rate = sum(live_rates) / len(live_rates)
            self._spec_accept_ewma = self._ewma_seed(
                self._spec_accept_ewma, mean_rate
            )
            self._spec_tokens_round_ewma = self._ewma_seed(
                self._spec_tokens_round_ewma,
                n_round_tokens / len(live_rates),
            )
            # Whole-batch fallback: speculation that keeps missing is a
            # strict loss (k drafter steps + a (k+1)-wide verify for ~1
            # token); after a few consecutive low-acceptance rounds,
            # drop to plain decode chunks for a cooldown window.
            if mean_rate < self._spec_min_accept:
                self._spec_low_streak += 1
                if self._spec_low_streak >= 3:
                    self._spec_cooldown = 50
            else:
                self._spec_low_streak = 0
        return chunk

    def _settle(self, chunk: "_Chunk") -> None:
        """Make the host agree with the device after `chunk` (a decode
        chunk or speculation round, read back: rows of `toks` are
        -1-padded past each slot's emissions): lengths, the slots that
        ended or were cancelled freed, the chunk's heirs live — all that
        the boundary's admission, `grow` and the next launch read, and
        nothing else. Walks the slots that were live when the chunk was
        dispatched: one that went live in its shadow has a padding row
        and `still` false here, and is the next chunk's. The tokens go
        out later (`_deliver`), behind the next launch: a slot is free
        before its last tokens and clean end are delivered, so a client
        that sees its stream finish and resubmits at once finds the
        capacity it released (max_pending=0 semantics)."""
        with self._lock:
            cancelled = set(self._cancelled)
        self._chunk = None
        rows = chunk.rows = chunk.toks.tolist()
        still = chunk.still.tolist()
        taken = [t.slot for t in self._tasks + chunk.heirs
                 if chunk.live[t.slot] is not None and still[t.slot]]
        if taken:
            raise RuntimeError(
                f"slots {taken} were admitted into as ending with the"
                " chunk in flight, and are still decoding"
            )
        lengths = self._lengths_host
        for slot, req in enumerate(chunk.live):
            if req is None:
                continue
            n_emitted = len(rows[slot]) - rows[slot].count(-1)
            lengths[slot] += n_emitted
            chunk.emitted += n_emitted
            gone = req.out in cancelled
            if still[slot] and not gone:
                continue
            # Free the slot under the submit lock. cancel() racing
            # normal completion must not leave a stale entry behind.
            with self._lock:
                self._live[slot] = None
                self._cancelled.discard(req.out)
                self._inflight.discard(req.out)
                self._release_slot_blocks(
                    slot, cache_tail=True, prompt=req.tokens,
                    namespace=(req.adapter or "").encode(),
                )
                self._release_adapter(req.out)
            if not gone:
                chunk.ended[slot] = self._slot_t0[slot]
                continue
            # The consumer is gone: nobody reads the chunk's tokens.
            chunk.live[slot] = None
            if still[slot]:
                # (One that ended anyway is retired on the device,
                # and its slot may already be its heir's there.)
                self.state = self._retire(slot)
            if req.trace is not None:
                req.trace.decode_steps += 1
                req.trace.decode_tokens += n_emitted
            self.recorder.finish(req.trace, "cancelled")
            req.out.put(None)
        if chunk.heirs:
            with self._lock:
                for task in chunk.heirs:
                    self._go_live(task)
            chunk.heirs.clear()
        self._decode_tokens += chunk.emitted
        self._settled = chunk

    def _deliver(self) -> None:
        """Hand the settled chunk's tokens to their consumers, after the
        first tokens they follow. With a decode launch in flight (the
        usual case: `_in_shadow`) the time is the child `fan_out/shadow`;
        with none (nothing left live, a round nothing survived, a
        force-retire at the boundary) the device waits for it."""
        chunk, self._settled = self._settled, None
        if chunk is None:
            return
        clock = self._clock
        clock.mark("barrier")
        self._wait_activations(chunk.activations)
        clock.mark("fan_out")
        if self._chunk is not None:
            with clock.child("shadow"):
                self._hand_out(chunk)
        else:
            self._hand_out(chunk)

    def _hand_out(self, chunk: "_Chunk") -> None:
        """A settled chunk's tokens into the queues of the requests it
        decoded, and the clean end behind the last tokens of those that
        ended in it."""
        for slot, req in enumerate(chunk.live):
            if req is None:
                continue
            n_emitted = 0
            for tok in chunk.rows[slot]:
                if tok >= 0:
                    req.out.put(tok)
                    n_emitted += 1
            if req.trace is not None:
                # Hot-path bookkeeping is attribute increments on the
                # preallocated trace slot — no allocation per chunk.
                req.trace.decode_steps += 1
                req.trace.decode_tokens += n_emitted
            if slot in chunk.ended:
                t_done = time.monotonic()
                self.recorder.finish(req.trace, "ok", t_done)
                req.out.put(None)
                self._turn_s = self._ewma(
                    self._turn_s, t_done - chunk.ended[slot],
                )
        if chunk.emitted:
            # One TPT sample per chunk: decode wall time amortized over
            # the tokens it emitted (the decode-isolation measurement
            # the disaggregation bench reads, labeled by engine role).
            self._tpt_hist.observe(chunk.seconds / chunk.emitted)


class _Chunk:
    """One decode chunk (or speculation round) from its launch to the
    delivery of its tokens (loop thread only). `_begin_chunk` freezes who
    is in it, the read-back adds what it emitted, `_settle` frees what
    ended and `_deliver` hands the tokens out behind the NEXT chunk's
    launch, when that chunk's record is already open: hence an object."""

    __slots__ = ("steps", "live", "ending", "activations", "heirs", "t_launch",
                 "toks", "still", "rows", "seconds", "emitted", "ended")

    def __init__(self, steps: int, live: List[Optional[_Request]],
                 ending: set, activations: List[_PrefillTask], t_launch: int):
        self.steps = steps
        # The request in each slot at the launch (None: not in the chunk;
        # the settlement also clears a cancelled one: nothing to deliver).
        self.live = live
        # Slots sure to end in it: the shadow may admit into them, and a
        # task finalized into one (taken out of `ending`) waits in `heirs`
        # for the settlement to go live.
        self.ending = ending
        self.heirs: List[_PrefillTask] = []
        # Tasks whose first token its tokens must not overtake.
        self.activations = activations
        self.t_launch = t_launch            # ns, the `dispatch` mark
        self.toks: Any = None               # (slots, steps), -1 padded
        self.still: Any = None              # (slots,) active after it
        self.rows: List[List[int]] = []     # `toks` as lists (settlement)
        self.seconds = 0.0                  # launch to read-back
        self.emitted = 0                    # tokens, over its slots
        self.ended: Dict[int, float] = {}   # ended slot -> when it went live


def prometheus_metrics(stats: Dict[str, Any]) -> str:
    """Render a stats() snapshot in Prometheus text exposition format.
    Every series here is declared in server/metrics_registry.py — the
    MET01 checker verifies these literals against it."""
    series = [
        ("dstack_tpu_serving_slots_active", "gauge", stats["active"]),
        ("dstack_tpu_serving_pending_requests", "gauge", stats["pending"]),
        ("dstack_tpu_serving_kv_blocks_in_use", "gauge",
         stats["kv_blocks_in_use"]),
        ("dstack_tpu_serving_kv_blocks_cached", "gauge",
         stats["kv_blocks_cached"]),
        ("dstack_tpu_serving_prefix_cache_hits_total", "counter",
         stats["prefix_cache_hits_total"]),
        ("dstack_tpu_serving_prefix_cache_misses_total", "counter",
         stats["prefix_cache_misses_total"]),
        # Hit-tier split (device + host + misses partitions every probe;
        # .get defaults keep pre-tier snapshots renderable, where every
        # hit was a device hit).
        ("dstack_tpu_serving_prefix_cache_device_hits_total", "counter",
         stats.get("prefix_cache_device_hits_total",
                   stats["prefix_cache_hits_total"])),
        ("dstack_tpu_serving_prefix_cache_host_hits_total", "counter",
         stats.get("prefix_cache_host_hits_total", 0)),
        ("dstack_tpu_serving_prefix_tokens_reused_total", "counter",
         stats["prefix_tokens_reused_total"]),
        ("dstack_tpu_serving_kv_cow_copies_total", "counter",
         stats["kv_cow_copies_total"]),
        # Hierarchical KV host tier + slot preemption (all zero without
        # kv_host_budget_bytes).
        ("dstack_tpu_serving_kv_host_blocks", "gauge",
         stats.get("kv_host_blocks", 0)),
        ("dstack_tpu_serving_kv_host_bytes", "gauge",
         stats.get("kv_host_bytes", 0)),
        ("dstack_tpu_serving_kv_spills_total", "counter",
         stats.get("kv_spills_total", 0)),
        ("dstack_tpu_serving_kv_host_evictions_total", "counter",
         stats.get("kv_host_evictions_total", 0)),
        ("dstack_tpu_serving_kv_swap_ins_total", "counter",
         stats.get("kv_swap_ins_total", 0)),
        ("dstack_tpu_serving_slots_swapped", "gauge",
         stats.get("slots_swapped", 0)),
        ("dstack_tpu_serving_slot_preemptions_total", "counter",
         stats.get("slot_preemptions_total", 0)),
        ("dstack_tpu_serving_slot_swap_ins_total", "counter",
         stats.get("slot_swap_ins_total", 0)),
        ("dstack_tpu_serving_prefill_chunks_total", "counter",
         stats["prefill_chunks_total"]),
        ("dstack_tpu_serving_prefill_tokens_total", "counter",
         stats["prefill_tokens_computed_total"]),
        ("dstack_tpu_serving_admitted_total", "counter",
         stats["admitted_total"]),
        # The engine loop's own accounting (.get defaults keep snapshots
        # from before the phase clock renderable).
        ("dstack_tpu_serving_loop_cycles_total", "counter",
         stats.get("loop_cycles_total", 0)),
        ("dstack_tpu_serving_loop_slow_cycles_total", "counter",
         stats.get("loop_slow_cycles_total", 0)),
        ("dstack_tpu_serving_decode_steps_total", "counter",
         stats.get("decode_steps_total", 0)),
        ("dstack_tpu_serving_decode_slot_steps_total", "counter",
         stats.get("decode_slot_steps_total", 0)),
        ("dstack_tpu_serving_decode_tokens_total", "counter",
         stats.get("decode_tokens_total", 0)),
        ("dstack_tpu_serving_decode_live_blocks_total", "counter",
         stats.get("decode_live_blocks_total", 0)),
        ("dstack_tpu_serving_decode_table_columns_total", "counter",
         stats.get("decode_table_columns_total", 0)),
        ("dstack_tpu_serving_decode_layer_blocks_total", "counter",
         stats.get("decode_layer_blocks_total", 0)),
        ("dstack_tpu_serving_decode_attended_blocks_total", "counter",
         stats.get("decode_attended_blocks_total", 0)),
        ("dstack_tpu_serving_decode_window_dead_blocks_total", "counter",
         stats.get("decode_window_dead_blocks_total", 0)),
        ("dstack_tpu_serving_kv_layer_blocks", "gauge",
         stats.get("kv_layer_blocks", 0)),
        ("dstack_tpu_serving_kv_window_dead_blocks", "gauge",
         stats.get("kv_window_dead_blocks", 0)),
        # Recurrent state beside the rows (zero for a model without
        # state-space layers).
        ("dstack_tpu_serving_kv_pool_layers", "gauge",
         stats.get("kv_pool_layers", 0)),
        ("dstack_tpu_serving_state_row_bytes", "gauge",
         stats.get("state_row_bytes", 0)),
        ("dstack_tpu_serving_state_pool_bytes", "gauge",
         stats.get("state_pool_bytes", 0)),
        ("dstack_tpu_serving_decode_state_rows_total", "counter",
         stats.get("decode_state_rows_total", 0)),
        ("dstack_tpu_serving_decode_state_rows_computed_total", "counter",
         stats.get("decode_state_rows_computed_total", 0)),
        # The expert bank held here and what routing asked of it.
        ("dstack_tpu_serving_moe_experts_published", "gauge",
         stats.get("moe_experts_published", 0)),
        ("dstack_tpu_serving_moe_experts_held", "gauge",
         stats.get("moe_experts_held", 0)),
        ("dstack_tpu_serving_moe_pairs_total", "counter",
         stats.get("moe_pairs_total", 0)),
        ("dstack_tpu_serving_moe_local_pairs_total", "counter",
         stats.get("moe_local_pairs_total", 0)),
        # Launches whose programs pass an expert layer, and those of them
        # whose expert layers took the routed path (moe.plan).
        ("dstack_tpu_serving_moe_expert_launches_total", "counter",
         stats.get("moe_expert_launches_total", 0)),
        ("dstack_tpu_serving_moe_routed_launches_total", "counter",
         stats.get("moe_routed_launches_total", 0)),
        ("dstack_tpu_serving_rejected_total", "counter",
         stats["rejected_total"]),
        # Speculative decoding (all zero when --spec-enable is off;
        # .get defaults keep pre-speculation snapshots renderable).
        ("dstack_tpu_serving_spec_rounds_total", "counter",
         stats.get("spec_rounds_total", 0)),
        ("dstack_tpu_serving_spec_fallback_rounds_total", "counter",
         stats.get("spec_fallback_rounds_total", 0)),
        ("dstack_tpu_serving_spec_tokens_proposed_total", "counter",
         stats.get("spec_tokens_proposed_total", 0)),
        ("dstack_tpu_serving_spec_tokens_accepted_total", "counter",
         stats.get("spec_tokens_accepted_total", 0)),
        ("dstack_tpu_serving_spec_tokens_rejected_total", "counter",
         stats.get("spec_tokens_rejected_total", 0)),
        ("dstack_tpu_serving_spec_draft_seconds_total", "counter",
         stats.get("spec_draft_seconds_total", 0.0)),
        ("dstack_tpu_serving_spec_verify_seconds_total", "counter",
         stats.get("spec_verify_seconds_total", 0.0)),
        ("dstack_tpu_serving_spec_accept_rate_ewma", "gauge",
         stats.get("spec_accept_rate_ewma", 0.0)),
        ("dstack_tpu_serving_spec_draft_len_mean", "gauge",
         stats.get("spec_draft_len_mean", 0.0)),
        # Prefill/decode disaggregation (all zero on a unified engine;
        # .get defaults keep pre-disaggregation snapshots renderable).
        ("dstack_tpu_serving_kv_handoffs_sent_total", "counter",
         stats.get("kv_handoffs_sent_total", 0)),
        ("dstack_tpu_serving_kv_handoffs_received_total", "counter",
         stats.get("kv_handoffs_received_total", 0)),
        ("dstack_tpu_serving_kv_handoffs_stale_rejected_total", "counter",
         stats.get("kv_handoffs_stale_rejected_total", 0)),
        ("dstack_tpu_serving_kv_transfer_bytes_total", "counter",
         stats.get("kv_transfer_bytes_total", 0)),
        ("dstack_tpu_serving_kv_transfer_queue_depth", "gauge",
         stats.get("kv_transfer_queue_depth", 0)),
        # Multi-tenant LoRA (zero when lora_max_adapters is 0; .get
        # defaults keep pre-LoRA snapshots renderable).
        ("dstack_tpu_serving_adapters_loaded", "gauge",
         stats.get("adapters_loaded", 0)),
        # Cold-start fast path (PR 20): process-wide jitted-program
        # builds (fresh compiles + persistent-cache retrievals — an
        # in-memory jit dispatch hit counts in neither) and the
        # persistent compile cache's hit/miss split. "Zero compile after
        # /readyz" means compiles_total not moving across a request.
        ("dstack_tpu_compile_cache_hits_total", "counter",
         stats.get("compile_cache_hits_total", 0)),
        ("dstack_tpu_compile_cache_misses_total", "counter",
         stats.get("compile_cache_misses_total", 0)),
        ("dstack_tpu_compile_seconds_total", "counter",
         stats.get("compile_seconds_total", 0)),
    ]
    lines = []
    for name, mtype, value in series:
        lines.append(f"# TYPE {name} {mtype}")
        lines.append(f"{name} {value}")
    # Ragged-attention dispatch counter, labeled by implementation path
    # (the registry declares the ("path",) label set).
    attn = "dstack_tpu_serving_attn_dispatch_total"
    lines.append(f"# TYPE {attn} counter")
    for path in ("pallas", "lax_ragged"):
        lines.append(
            f'{attn}{{path="{path}"}}'
            f' {stats.get(f"attn_dispatch_{path}_total", 0)}'
        )
    # Host wall seconds of the engine loop per phase (children as
    # "admit/match"): the phases but `wait` sum to the loop's busy time.
    loop = "dstack_tpu_serving_loop_phase_seconds_total"
    lines.append(f"# TYPE {loop} counter")
    for key, name in _LOOP_SECONDS_KEYS.items():
        lines.append(f'{loop}{{phase="{key}"}} {stats.get(name, 0.0)}')
    # Latency histograms, labeled with the engine role: a split
    # request's prefill leg (submit -> handoff acked), decode leg
    # (receipt -> first delivery) and a unified engine's full TTFT are
    # different quantities — the label keeps scrapers from aggregating
    # them into one meaningless distribution. Older stats snapshots
    # without ttft_hist degrade to the sum/count pair.
    role = stats.get("role", "unified")

    def _render_hist(base: str, hist: Dict[str, Any], hist_role: str = "",
                     emit_type: bool = True) -> None:
        r = hist_role or role
        if emit_type:
            lines.append(f"# TYPE {base} histogram")
        for le, cumulative in hist["buckets"]:
            lines.append(
                f'{base}_bucket{{le="{le}",role="{r}"}} {cumulative}'
            )
        lines.append(
            f'{base}_bucket{{le="+Inf",role="{r}"}} {hist["count"]}'
        )
        lines.append(f'{base}_sum{{role="{r}"}} {hist["sum"]}')
        lines.append(f'{base}_count{{role="{r}"}} {hist["count"]}')

    _render_hist(
        "dstack_tpu_serving_ttft_seconds",
        stats.get("ttft_hist") or {
            "buckets": [],
            "sum": stats["ttft_seconds_sum"],
            "count": stats["admitted_total"],
        },
    )
    # Cold-start leg of the same series: the first token a warmup-less
    # boot delivered (the sample that paid compilation). Same base name,
    # so the TYPE line above already covers it; warmup-gated boots keep
    # this bucket empty by construction.
    cold = stats.get("ttft_cold_hist")
    if cold:
        _render_hist(
            "dstack_tpu_serving_ttft_seconds", cold,
            hist_role="cold_start", emit_type=False,
        )
    _render_hist(
        "dstack_tpu_serving_tpt_seconds",
        stats.get("tpt_hist") or {"buckets": [], "sum": 0.0, "count": 0},
    )
    _render_hist(
        "dstack_tpu_serving_kv_transfer_seconds",
        stats.get("kv_transfer_hist")
        or {"buckets": [], "sum": 0.0, "count": 0},
    )
    # Host-tier swap-in latency (block resurrections + whole-slot
    # readmissions): the number to compare against a cold re-prefill of
    # the same prefix when tuning kv_host_budget_bytes.
    _render_hist(
        "dstack_tpu_serving_kv_swap_in_seconds",
        stats.get("swap_in_hist")
        or {"buckets": [], "sum": 0.0, "count": 0},
    )
    # Warmup wall time (one sample per warmup() call — engines usually
    # warm once per boot, so count doubles as "did this engine warm").
    # Label-less: warmup happens before any request exists to attribute.
    wh = stats.get("warmup_hist") or {"buckets": [], "sum": 0.0, "count": 0}
    wb = "dstack_tpu_serving_warmup_seconds"
    lines.append(f"# TYPE {wb} histogram")
    for le, cumulative in wh["buckets"]:
        lines.append(f'{wb}_bucket{{le="{le}"}} {cumulative}')
    lines.append(f'{wb}_bucket{{le="+Inf"}} {wh["count"]}')
    lines.append(f'{wb}_sum {wh["sum"]}')
    lines.append(f'{wb}_count {wh["count"]}')
    # Per-request phase breakdown (PR 15 flight recorder): one histogram
    # per phase the recorder observed, labeled {phase, role}. Engines
    # with the recorder off (or older snapshots) emit nothing — scrapers
    # treat an absent series as zero, and MET01 only pins declared names.
    phase_hists = stats.get("phase_hists") or {}
    if phase_hists:
        base = "dstack_tpu_serving_phase_seconds"
        lines.append(f"# TYPE {base} histogram")
        for phase in sorted(phase_hists):
            hist = phase_hists[phase]
            labels = f'phase="{phase}",role="{role}"'
            for le, cumulative in hist["buckets"]:
                lines.append(
                    f'{base}_bucket{{le="{le}",{labels}}} {cumulative}'
                )
            lines.append(
                f'{base}_bucket{{le="+Inf",{labels}}} {hist["count"]}'
            )
            lines.append(f'{base}_sum{{{labels}}} {hist["sum"]}')
            lines.append(f'{base}_count{{{labels}}} {hist["count"]}')
    return "\n".join(lines) + "\n"
