"""Framework-native model server: the continuous-batching engine behind an
OpenAI-compatible HTTP API.

The JetStream/vLLM examples bring external engines; this one serves the
same llama-family checkpoints with dstack-tpu's own KV-cache decode loop
(workloads/generate.py) — the whole stack, orchestrator to tokens, is this
repo. Endpoints: GET /v1/models, POST /v1/chat/completions
(stream and non-stream), served by the continuous-batching engine
(workloads/serving.py).

The tokenizer here is a toy byte-level one so the example runs without
downloading a vocab (zero-egress test environments); swap in your
tokenizer for real checkpoints.
"""

import argparse
import codecs
import itertools
import json
import math
import os
import sys
import threading
import time
import traceback
from collections import defaultdict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax

from dstack_tpu.dataplane.qos import (
    DEFAULT_TENANT,
    QoSGate,
    TenantShedError,
)
from dstack_tpu.server.tracing import HistogramData
from dstack_tpu.utils.stagemarkers import auto_stage
from dstack_tpu.utils.tracecontext import ensure_request_trace
from dstack_tpu.workloads import compile_cache
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.lora_serving import (
    AdapterBusyError,
    AdapterPoolFullError,
)
from dstack_tpu.workloads.serving import (
    EngineBusyError,
    EngineOverloadedError,
    ServingEngine,
    prometheus_metrics,
)
from dstack_tpu.workloads.transformer import init_params


class Engine:
    # Prompt lengths are bucketed so each bucket compiles ONCE — a fresh
    # XLA compile per novel prompt length would dominate request latency.
    MIN_BUCKET = 32

    def __init__(self, preset: str, max_new_tokens: int, checkpoint_dir: str = "",
                 quantize: str = "none", max_pending: int = 16,
                 slots: int = 8, steps_per_sync: int = 4,
                 max_prefills_per_chunk: int = 4,
                 prefill_chunk_tokens: int = 128, kv_block_size: int = 16,
                 spec_enable: bool = False, spec_max_draft: int = 4,
                 spec_draft_preset: str = "int8", kv_budget_mb: int = 0,
                 role: str = "unified", mesh_model: int = 1,
                 kv_transfer_connect: str = "",
                 lora_max_adapters: int = 0, lora_rank: int = 8,
                 adapters=None, qos_rate: float = 0.0,
                 qos_burst: float = 20.0, qos_tenant_cap: int = 64,
                 qos_weights=None, kv_host_budget_mb: int = 0,
                 max_resident_slots: int = 0,
                 trace_ring: int = 256, trace_slow_ms=None, layers: int = 0):
        self.config = PRESETS[preset]
        if layers > 0:
            self.config = self.config.with_(n_layers=layers)
        if max_new_tokens >= self.config.max_seq_len:
            raise SystemExit(
                f"--max-new-tokens {max_new_tokens} must be <"
                f" max_seq_len {self.config.max_seq_len} for {preset}"
            )
        self.max_new_tokens = max_new_tokens
        self._handoff_ids = itertools.count(1)
        auto_stage("weights_start")
        t_weights = time.monotonic()
        weights_via = "init"
        if checkpoint_dir:
            from dstack_tpu.workloads import checkpoint as ckpt

            # Cold-start order: packed export first (mmap + parallel
            # device_put — the scale-from-zero fast path), then the
            # params-only Orbax export, then a full train-state restore.
            params = ckpt.load_packed(checkpoint_dir)
            if params is not None:
                weights_via = "packed-parallel"
            else:
                from dstack_tpu.workloads.transformer import init_params as _init

                template = _init(self.config, jax.random.PRNGKey(0))
                params = ckpt.restore_exported_params(checkpoint_dir, template)
                if params is not None:
                    weights_via = "orbax-export"
                else:
                    from dstack_tpu.workloads.train import init_train_state

                    state_tpl = init_train_state(
                        self.config, jax.random.PRNGKey(0)
                    )
                    restored = ckpt.restore_latest(checkpoint_dir, state_tpl)
                    if restored is not None:
                        params = restored.params
                        weights_via = "orbax-train"
                    else:
                        params = template
            self.params = params
        else:
            self.params = init_params(self.config, jax.random.PRNGKey(0))
        jax.block_until_ready(jax.tree_util.tree_leaves(self.params)[0])
        auto_stage("weights_end")
        self.weights_seconds = time.monotonic() - t_weights
        self.weights_via = weights_via
        print(
            f"weights: loaded in {self.weights_seconds:.2f}s"
            f" via {weights_via}", flush=True,
        )
        if quantize == "int8":
            # Weight-only int8: decode is weight-bandwidth-bound, so the
            # smaller HBM reads buy ~1.25x decode throughput (measured on
            # v5e) at ~half the weight memory (workloads/quant.py).
            from dstack_tpu.workloads.quant import quantize_params

            self.params = quantize_params(self.params)
        # Continuous batching: concurrent requests share one decode batch
        # (workloads/serving.py) instead of queueing behind each other.
        # Bounded admission: beyond max_pending queued requests the API
        # answers 429 + Retry-After rather than letting TTFT blow up
        # (measured: 10.8 s TTFT p50 at 2x oversubscription unbounded).
        # Scheduler knobs ride through from the CLI: `slots` (decode
        # batch width), `steps_per_sync` (device steps per host
        # readback), and `max_prefills_per_chunk` (admissions per chunk
        # boundary — the overlapped scheduler's fairness knob). See
        # docs/guides/serving-tuning.md for the measured trade-offs.
        # Paged-KV knobs: `prefill_chunk_tokens` bounds the prompt
        # tokens computed per chunk boundary (decode stall ceiling), and
        # `kv_block_size` is the pool's block granularity (must divide
        # the preset's max_seq_len). The engine validates both; surface
        # its ValueError as a clean CLI error, not a traceback.
        # Speculative decoding: the drafter is either an int8-quantized
        # copy of the target (default — same architecture, cheaper math,
        # high acceptance) or a smaller preset drafting for a bigger
        # target. The engine builds the int8 drafter itself when no
        # drafter params are passed.
        draft_params = draft_config = None
        if spec_enable and spec_draft_preset != "int8":
            draft_config = PRESETS[spec_draft_preset]
            draft_params = init_params(draft_config, jax.random.PRNGKey(1))
        # Tensor parallelism: shard the target (and drafter) weights plus
        # the paged KV pools over a `model` mesh axis. The column-parallel
        # specs keep contractions replicated, so a sharded server is
        # token-bit-exact with the single-device one (no logic forks).
        mesh = None
        if mesh_model > 1:
            from dstack_tpu.workloads.sharding import make_mesh

            devs = jax.devices()
            if len(devs) < mesh_model:
                raise SystemExit(
                    f"--mesh-model {mesh_model} needs that many devices,"
                    f" have {len(devs)}"
                )
            mesh = make_mesh(devs[:mesh_model], model=mesh_model)
        # Prefill/decode disaggregation: a prefill-tier server computes
        # chunked prefill on its own devices and ships finished KV blocks
        # to the decode tier over the kv_transfer seam; its chat API acks
        # with finish_reason "kv_handoff" (tokens stream from the decode
        # tier — see /v1/handoffs/<id> there).
        kv_transfer = None
        if role == "prefill":
            if not kv_transfer_connect:
                raise SystemExit(
                    "--role prefill requires --kv-transfer-connect host:port"
                )
            from dstack_tpu.workloads.kv_transfer import TransferClient

            host, _, port = kv_transfer_connect.rpartition(":")
            try:
                kv_transfer = TransferClient(host or "127.0.0.1", int(port))
            except ValueError:
                raise SystemExit(
                    f"--kv-transfer-connect {kv_transfer_connect!r} is not"
                    " host:port"
                )
        try:
            self.serving = ServingEngine(
                self.config, self.params, slots=slots, temperature=0.8,
                max_pending=max_pending, steps_per_sync=steps_per_sync,
                max_prefills_per_chunk=max_prefills_per_chunk,
                prefill_chunk_tokens=prefill_chunk_tokens,
                kv_block_size=kv_block_size,
                spec_enable=spec_enable, spec_max_draft=spec_max_draft,
                spec_draft_params=draft_params,
                spec_draft_config=draft_config,
                kv_budget_bytes=kv_budget_mb * (1 << 20) or None,
                mesh=mesh, role=role, kv_transfer=kv_transfer,
                lora_max_adapters=lora_max_adapters, lora_rank=lora_rank,
                trace_ring=trace_ring, trace_slow_ms=trace_slow_ms,
                # Hierarchical KV: LRU-evicted prefix blocks spill to a
                # host-RAM tier instead of dying, and admitted streams may
                # overcommit the HBM-resident slot count (preempted slots
                # swap their whole KV chain to host and resume later).
                kv_host_budget_bytes=kv_host_budget_mb * (1 << 20) or None,
                max_resident_slots=max_resident_slots or None,
                qos_weights=qos_weights or None,
            )
        except ValueError as e:
            raise SystemExit(f"invalid serving configuration: {e}")
        # --adapter name=path entries: "random" makes a demo adapter in
        # process (tests, zero-egress environments); anything else is a
        # save_adapter npz carrying its own rank/alpha.
        self.lora_rank = lora_rank
        for entry in adapters or ():
            name, _, path = entry.partition("=")
            if not name or not path:
                raise SystemExit(f"--adapter {entry!r} is not name=path")
            try:
                self.load_adapter(name, path)
            except (ValueError, RuntimeError, OSError) as e:
                raise SystemExit(f"--adapter {entry!r}: {e}")
        # Per-tenant QoS in front of submit: token buckets shed floods
        # (429 + Retry-After), the DRR queue orders admission under
        # contention for the decode slots. Off unless --qos-rate > 0.
        self.qos = None
        if qos_rate > 0:
            self.qos = QoSGate(
                rate=qos_rate, burst=qos_burst, tenant_cap=qos_tenant_cap,
                weights=qos_weights or None,
                concurrency=max(slots, max_pending),
            )
        # Per-tenant observability (bounded cardinality via the gate's
        # TenantLabels when QoS is on, else a private mapping).
        from dstack_tpu.dataplane.qos import TenantLabels

        self.tenant_labels = (
            self.qos.labels if self.qos is not None
            else TenantLabels(cap=qos_tenant_cap)
        )
        self._tenant_lock = threading.Lock()
        self.tenant_requests = defaultdict(int)
        self.tenant_shed = defaultdict(int)
        self.tenant_ttft = defaultdict(HistogramData)

    def load_adapter(self, name: str, path: str, alpha: float = 16.0) -> int:
        """Load a LoRA adapter into the pool: `path` is a save_adapter
        npz, or the literal "random" for an in-process demo adapter.
        Returns the device pool slot the adapter landed in."""
        from dstack_tpu.workloads.lora_serving import (
            demo_adapter, load_adapter_file,
        )

        if path == "random":
            seed = abs(hash(name)) % (2 ** 31)
            tree = demo_adapter(
                self.config, self.params, jax.random.PRNGKey(seed),
                rank=self.lora_rank, targets=("wq", "wv"),
            )
            return self.serving.load_adapter(name, tree, alpha=alpha)
        tree, rank, file_alpha = load_adapter_file(path)
        if rank != self.lora_rank:
            raise ValueError(
                f"adapter {name!r} has rank {rank}, engine pool is"
                f" rank {self.lora_rank}"
            )
        return self.serving.load_adapter(name, tree, alpha=file_alpha)

    def record_tenant(self, tenant: str, *, shed: bool = False,
                      ttft: float = None) -> None:
        label = self.tenant_labels.label(tenant or DEFAULT_TENANT)
        with self._tenant_lock:
            if shed:
                self.tenant_shed[label] += 1
            else:
                self.tenant_requests[label] += 1
            if ttft is not None:
                self.tenant_ttft[label].observe(ttft)

    def tenant_metrics_lines(self) -> list:
        """Per-tenant Prometheus series appended to the engine's
        exposition (series declared in server/metrics_registry.py)."""
        lines = []
        with self._tenant_lock:
            req = sorted(self.tenant_requests.items())
            shed = sorted(self.tenant_shed.items())
            ttft = sorted(
                (t, h.to_dict()) for t, h in self.tenant_ttft.items()
            )
        lines.append("# TYPE dstack_tpu_serving_tenant_requests_total counter")
        for t, n in req:
            lines.append(
                f'dstack_tpu_serving_tenant_requests_total{{tenant="{t}"}} {n}'
            )
        lines.append("# TYPE dstack_tpu_serving_tenant_shed_total counter")
        for t, n in shed:
            lines.append(
                f'dstack_tpu_serving_tenant_shed_total{{tenant="{t}"}} {n}'
            )
        base = "dstack_tpu_serving_tenant_ttft_seconds"
        lines.append(f"# TYPE {base} histogram")
        for t, h in ttft:
            for le, cum in h["buckets"]:
                lines.append(
                    f'{base}_bucket{{le="{le}",tenant="{t}"}} {cum}'
                )
            lines.append(
                f'{base}_bucket{{le="+Inf",tenant="{t}"}} {h["count"]}'
            )
            lines.append(f'{base}_sum{{tenant="{t}"}} {h["sum"]}')
            lines.append(f'{base}_count{{tenant="{t}"}} {h["count"]}')
        return lines

    def encode(self, text: str):
        ids = [min(b, self.config.vocab_size - 1) for b in text.encode()] or [0]
        limit = self.config.max_seq_len - self.max_new_tokens
        ids = ids[-limit:] if limit > 0 else ids[:1]
        # Bucket to a power of two: pad short prompts left with newline
        # bytes, truncate the OLDEST bytes down to the bucket otherwise.
        bucket = self.MIN_BUCKET
        while bucket * 2 <= len(ids):
            bucket *= 2
        bucket = min(bucket, limit if limit > 0 else bucket)
        if len(ids) < bucket:
            ids = [10] * (bucket - len(ids)) + ids
        else:
            ids = ids[-bucket:]
        # Host-side (1, bucket) nested list, NOT a device array: the
        # engine takes a token list, and a device round-trip here would
        # build four tiny jit programs per novel bucket — compiles the
        # warmup pass can't see, breaking the zero-post-ready contract.
        return [ids]

    def decode(self, ids) -> str:
        return bytes(int(t) % 256 for t in ids).decode("utf-8", errors="replace")

    def chat_stream(self, messages, max_tokens=None, temperature=None,
                    top_p=None, stop=None, usage_out=None,
                    adapter=None, tenant=None,
                    traceparent=None, x_request_id=None):
        """Yield decoded text fragments as tokens land (continuous batch).

        `max_tokens` and `temperature` are the per-request OpenAI fields:
        the budget is clamped to the server's --max-new-tokens cap (which
        also bounds the KV rows a request can occupy); temperature rides
        per-SLOT through the decode batch (0 = greedy). UTF-8 is decoded
        incrementally so multi-byte characters split across tokens
        reassemble instead of degrading to U+FFFD."""
        budget = self.max_new_tokens
        if max_tokens is not None:
            try:
                budget = max(1, min(int(max_tokens), self.max_new_tokens))
            except (TypeError, ValueError):
                pass  # malformed client value: serve with the server cap
        temp = None
        if temperature is not None:
            try:
                v = float(temperature)
                # max(0.0, nan) is 0.0 — NaN would silently mean GREEDY
                # instead of "malformed: engine default" (the engine
                # itself rejects NaN with 400; match the top_p branch).
                if math.isfinite(v):
                    temp = max(0.0, v)
            except (TypeError, ValueError):
                pass  # malformed: engine default
        nucleus = 1.0
        if top_p is not None:
            try:
                v = float(top_p)
                # NaN slips through min/max (max(nan, x) is nan): treat it
                # like any other malformed value — no filtering.
                if v == v:
                    nucleus = min(max(v, 1e-6), 1.0)
            except (TypeError, ValueError):
                pass  # malformed: no filtering
        prompt = "\n".join(
            f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages
        )
        if isinstance(stop, str):
            stops = [stop]
        elif isinstance(stop, (list, tuple)):
            stops = [x for x in stop if isinstance(x, str) and x]
        else:
            stops = []  # malformed: no stop filtering (lenient like temp)
        tokens = self.encode(prompt + "\nassistant:")
        if usage_out is not None:
            # OpenAI usage accounting: real engine token counts, not a
            # re-tokenization guess (byte vocab: one token per byte).
            usage_out["prompt_tokens"] = len(tokens[0])
            usage_out["completion_tokens"] = 0
        rid = None
        if self.serving.role == "prefill":
            # Correlation id carried on the KV handoff: the front-end
            # fetches the stream from the decode tier at
            # GET /v1/handoffs/<id>.
            rid = next(self._handoff_ids)
            if usage_out is not None:
                usage_out["handoff_id"] = rid
        # Arrival timestamp BEFORE QoS admission, so the flight recorder
        # can attribute gate time to its own `qos_admission` phase.
        t_arrival = time.monotonic()
        granted = False
        if self.qos is not None:
            # Sheds (TenantShedError -> 429) or blocks for the tenant's
            # DRR turn at a grant permit; the permit frees in `finally`.
            try:
                self.qos.admit(tenant or DEFAULT_TENANT)
            except TenantShedError:
                # Shed before the engine ever saw it: a one-shot terminal
                # trace so the tail capture still records the rejection.
                self.serving.recorder.record_dropped(
                    x_request_id, x_request_id=x_request_id,
                    traceparent=traceparent, t0=t_arrival,
                )
                raise
            granted = True
        t_submit = time.monotonic()
        ttft_seen = False
        try:
            out = self.serving.submit(
                list(tokens[0]), max_new_tokens=budget,
                temperature=temp, top_p=nucleus, request_id=rid,
                adapter=adapter, traceparent=traceparent,
                x_request_id=x_request_id,
                t_arrival=t_arrival if self.qos is not None else None,
                # On a host-tier engine with --qos-weight, a heavier
                # tenant's request may preempt a lighter tenant's live
                # slot (KV swap-out) instead of queueing behind it.
                tenant=tenant or DEFAULT_TENANT,
            )
        except BaseException:
            if granted:
                self.qos.release()
            raise
        self.record_tenant(tenant)
        dec = codecs.getincrementaldecoder("utf-8")("replace")
        # Streaming stop matching: text already sent cannot be unsent, so
        # hold back any suffix that is a PREFIX of a stop sequence until
        # it either completes the stop (truncate + free the slot) or
        # diverges (flush). The buffer never exceeds max stop length + one
        # piece, so scans are O(stop length) per token, and OpenAI
        # semantics hold: the stop string itself is never emitted.
        max_hold = max((len(x) for x in stops), default=1) - 1

        def holdback(b):
            for k in range(min(max_hold, len(b)), 0, -1):
                tail = b[-k:]
                if any(x.startswith(tail) for x in stops):
                    return k
            return 0

        buf = ""
        try:
            while True:
                tok = out.get()
                if isinstance(tok, BaseException):
                    raise RuntimeError(f"generation failed: {tok}")
                if tok is None:
                    buf += dec.decode(b"", True)
                    if buf:
                        yield buf  # incomplete stop prefix at end: emit
                    if (self.serving.role == "prefill" and budget > 1
                            and usage_out is not None
                            and not usage_out.get("completion_tokens")):
                        # Handed off: the prefill tier never streams
                        # tokens (the sampled first token travels inside
                        # the KV handoff); this response is the ack.
                        usage_out["finish_reason"] = "kv_handoff"
                    return
                if not ttft_seen:
                    ttft_seen = True
                    self.record_tenant(
                        tenant, ttft=time.monotonic() - t_submit
                    )
                if usage_out is not None:
                    usage_out["completion_tokens"] += 1
                piece = dec.decode(bytes([int(tok) % 256]))
                if not piece:
                    continue
                if not stops:
                    yield piece
                    continue
                buf += piece
                hit = -1
                for x in stops:
                    i = buf.find(x)
                    if i >= 0 and (hit < 0 or i < hit):
                        hit = i
                if hit >= 0:
                    if buf[:hit]:
                        yield buf[:hit]
                    if usage_out is not None:
                        # OpenAI semantics: clients branch on this —
                        # "length" makes them retry/continue a completion
                        # that actually ended cleanly on a stop sequence.
                        usage_out["finish_reason"] = "stop"
                    self.serving.cancel(out)  # free the slot early
                    return
                keep = holdback(buf)
                if len(buf) > keep:
                    yield buf[:len(buf) - keep]
                    buf = buf[len(buf) - keep:] if keep else ""
        finally:
            # Consumer gone mid-stream (client disconnect closes this
            # generator) or stop hit: the engine must not keep decoding
            # into a queue nobody reads. Idempotent after clean end.
            self.serving.cancel(out)
            if granted:
                self.qos.release()

    def chat(self, messages, max_tokens=None, temperature=None, top_p=None,
             stop=None, usage_out=None, adapter=None, tenant=None,
             traceparent=None, x_request_id=None) -> str:
        return "".join(self.chat_stream(messages, max_tokens, temperature,
                                        top_p, stop, usage_out=usage_out,
                                        adapter=adapter, tenant=tenant,
                                        traceparent=traceparent,
                                        x_request_id=x_request_id))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--preset", default="smol-1b", choices=sorted(PRESETS),
        help="a ModelConfig of workloads/config.py. A preset with state-space"
             " layers (`tiny-mamba`; ModelConfig's attn_layer_period,"
             " attn_layer_offset, mamba_d_state, mamba_d_conv, mamba_expand,"
             " mamba_dt_rank, use_rope, tie_embeddings: the `jamba` block) keeps"
             " a recurrent state a slot beside the KV blocks: its engine runs"
             " with prefix reuse off (/metrics `prefix_cache` says why) and"
             " refuses, with a ValueError at start-up, --spec-enable,"
             " --quantize int8, --lora-max-adapters, --mesh-model > 1, --role"
             " prefill / decode and --kv-host-budget-mb (so"
             " --max-resident-slots too): each would drop or have to roll"
             " back the state. `tiny-laguna` (query heads and a per-head"
             " output gate by layer kind, a dense layer beside a window/full"
             " pattern, sigmoid-routed experts of which `experts_held` may"
             " be a device's share: /metrics `moe_experts_held`,"
             " `moe_local_pairs_total`) refuses the same options")
    parser.add_argument("--layers", type=int, default=0,
                        help="serve the preset cut to this many layers"
                             " (0 = the preset's depth); must match the"
                             " checkpoint's")
    parser.add_argument("--port", type=int, default=9000)
    parser.add_argument("--model-name", default="dstack-tpu-native")
    parser.add_argument("--max-new-tokens", type=int, default=64)
    parser.add_argument("--checkpoint-dir", default="",
                        help="volume path with a checkpoint to serve: a"
                             " save_packed export (mmap + parallel load,"
                             " the cold-start fast path) or an Orbax"
                             " checkpoint")
    parser.add_argument("--compile-cache-dir", default="",
                        help="persistent XLA compile-cache base dir (a"
                             " durable volume path): repeat boots retrieve"
                             " compiled programs from disk instead of"
                             " recompiling. Keyed by jax+jaxlib version"
                             " and backend under the base, so one volume"
                             " serves heterogeneous workers. An exported"
                             " JAX_COMPILATION_CACHE_DIR wins over this"
                             " flag; unset, $DSTACK_TPU_COMPILE_CACHE,"
                             " then .jax-compile-cache/ in the checkout")
    parser.add_argument("--no-warmup", action="store_true",
                        help="skip the warmup pass that pre-compiles every"
                             " jitted engine program before /readyz flips"
                             " ready (warmup is on by default; without it"
                             " the first unlucky requests pay compilation)")
    parser.add_argument("--quantize", default="none", choices=["none", "int8"],
                        help="weight-only int8 for ~1.25x decode throughput")
    parser.add_argument("--max-pending", type=int, default=16,
                        help="queued-request bound; overflow answers 429")
    parser.add_argument("--slots", type=int, default=8,
                        help="decode batch width (concurrent streams)")
    parser.add_argument("--steps-per-sync", type=int, default=4,
                        help="device decode steps per host readback")
    parser.add_argument("--max-prefills-per-chunk", type=int, default=4,
                        help="admissions per decode chunk boundary (the"
                             " overlapped scheduler's fairness knob)")
    parser.add_argument("--prefill-chunk-tokens", type=int, default=128,
                        help="prompt tokens computed per chunk boundary —"
                             " bounds the decode stall a long prompt causes")
    parser.add_argument("--kv-block-size", type=int, default=16,
                        help="paged-KV block granularity in tokens; must"
                             " divide the preset's max_seq_len")
    parser.add_argument("--spec-enable", action="store_true",
                        help="draft-model speculative decoding: a cheap"
                             " drafter proposes tokens, the target verifies"
                             " them in one forward (distribution-exact)")
    parser.add_argument("--spec-max-draft", type=int, default=4,
                        help="ceiling for the adaptive per-slot draft length")
    parser.add_argument("--spec-draft-preset", default="int8",
                        help="drafter model: 'int8' (quantized copy of the"
                             " target) or a smaller preset name")
    parser.add_argument("--role", default="unified",
                        choices=["unified", "prefill", "decode"],
                        help="serving tier: unified (default) runs prefill"
                             " and decode in-process; prefill ships finished"
                             " KV blocks to the decode tier; decode admits"
                             " handed-off requests on --kv-transfer-port")
    parser.add_argument("--mesh-model", type=int, default=1,
                        help="tensor-parallel shards over a `model` mesh"
                             " axis (weights + paged KV pools; bit-exact"
                             " with 1)")
    parser.add_argument("--kv-transfer-port", type=int, default=0,
                        help="decode role: port the KV transfer server"
                             " listens on for prefill-tier handoffs")
    parser.add_argument("--kv-transfer-connect", default="",
                        help="prefill role: host:port of the decode tier's"
                             " KV transfer server")
    parser.add_argument("--kv-budget-mb", type=int, default=0,
                        help="KV pool memory budget in MiB (0 = unlimited);"
                             " with --spec-enable the target AND drafter"
                             " pools must both fit")
    parser.add_argument("--kv-host-budget-mb", type=int, default=0,
                        help="host-RAM KV tier budget in MiB (0 = no host"
                             " tier): LRU-evicted prefix-cache blocks spill"
                             " here instead of dying, and preempted slots"
                             " park their live KV chain here until resume")
    parser.add_argument("--max-resident-slots", type=int, default=0,
                        help="HBM-resident decode slot cap (0 = --slots):"
                             " setting it below --slots overcommits"
                             " admission — the engine round-robins more"
                             " admitted streams than fit in HBM by swapping"
                             " slot KV through the host tier (requires"
                             " --kv-host-budget-mb)")
    parser.add_argument("--qos-weight", action="append", default=[],
                        metavar="TENANT=WEIGHT",
                        help="per-tenant DRR weight (repeatable; default"
                             " 1.0): orders admission under contention and,"
                             " with --kv-host-budget-mb, lets a heavier"
                             " tenant preempt a lighter tenant's live slot"
                             " (KV swap-out) mid-generation")
    parser.add_argument("--adapter", action="append", default=[],
                        metavar="NAME=PATH",
                        help="preload a LoRA adapter (repeatable);"
                             " PATH is an .npz from save_adapter, or"
                             " 'random' for a demo adapter. Request it"
                             " via model='<model-name>:<NAME>'")
    parser.add_argument("--lora-max-adapters", type=int, default=0,
                        help="device adapter-pool slots; 0 disables LoRA"
                             " multiplexing (defaults to len(--adapter)"
                             " when adapters are given)")
    parser.add_argument("--lora-rank", type=int, default=8,
                        help="rank of the device adapter pool; every"
                             " loaded adapter must match it")
    parser.add_argument("--qos-rate", type=float, default=0.0,
                        help="per-tenant token-bucket refill rate"
                             " (requests/s); 0 disables QoS admission")
    parser.add_argument("--qos-burst", type=float, default=20.0,
                        help="per-tenant token-bucket capacity")
    parser.add_argument("--qos-tenant-cap", type=int, default=64,
                        help="distinct tenant labels before metrics"
                             " collapse into the overflow label")
    parser.add_argument("--trace-ring", type=int, default=256,
                        help="flight-recorder ring size (retained request"
                             " traces); 0 disables per-request tracing")
    parser.add_argument("--trace-slow-ms", type=float, default=None,
                        help="tail-based capture threshold: full traces"
                             " persist only for requests at/above this"
                             " many ms or ending in error/shed (unset"
                             " disables tail capture)")
    args = parser.parse_args()
    if args.adapter and args.lora_max_adapters <= 0:
        args.lora_max_adapters = len(args.adapter)
    if args.spec_max_draft <= 0:
        raise SystemExit(
            f"--spec-max-draft must be positive, got {args.spec_max_draft}"
        )
    if args.spec_draft_preset != "int8" and args.spec_draft_preset not in PRESETS:
        raise SystemExit(
            f"--spec-draft-preset {args.spec_draft_preset!r} is not a known"
            f" preset (choose 'int8' or one of: {', '.join(sorted(PRESETS))})"
        )
    if args.prefill_chunk_tokens <= 0:
        raise SystemExit(
            f"--prefill-chunk-tokens must be positive,"
            f" got {args.prefill_chunk_tokens}"
        )
    if args.kv_block_size <= 0:
        raise SystemExit(
            f"--kv-block-size must be positive, got {args.kv_block_size}"
        )
    max_len = PRESETS[args.preset].max_seq_len
    if max_len % args.kv_block_size != 0:
        raise SystemExit(
            f"--kv-block-size {args.kv_block_size} must divide"
            f" {args.preset}'s max_seq_len {max_len}"
        )

    if args.role == "decode" and not args.kv_transfer_port:
        raise SystemExit("--role decode requires --kv-transfer-port")
    if args.max_resident_slots and not args.kv_host_budget_mb:
        raise SystemExit(
            "--max-resident-slots overcommit needs --kv-host-budget-mb"
            " (swapped-out slots park their KV in the host tier)"
        )
    qos_weights = {}
    for entry in args.qos_weight:
        tenant, _, weight = entry.partition("=")
        try:
            qos_weights[tenant] = float(weight)
        except ValueError:
            weight = ""
        if not tenant or not weight or qos_weights[tenant] <= 0:
            raise SystemExit(
                f"--qos-weight {entry!r} is not TENANT=WEIGHT"
                " with a positive weight"
            )
    # The cache must be live before the Engine constructor touches the
    # accelerator — weight init and the warmup pass below both compile.
    print(f"compile cache: {compile_cache.enable(args.compile_cache_dir)}",
          flush=True)
    engine = Engine(args.preset, args.max_new_tokens, args.checkpoint_dir,
                    quantize=args.quantize, max_pending=args.max_pending,
                    slots=args.slots, steps_per_sync=args.steps_per_sync,
                    max_prefills_per_chunk=args.max_prefills_per_chunk,
                    prefill_chunk_tokens=args.prefill_chunk_tokens,
                    kv_block_size=args.kv_block_size,
                    spec_enable=args.spec_enable,
                    spec_max_draft=args.spec_max_draft,
                    spec_draft_preset=args.spec_draft_preset,
                    kv_budget_mb=args.kv_budget_mb,
                    role=args.role, mesh_model=args.mesh_model,
                    kv_transfer_connect=args.kv_transfer_connect,
                    lora_max_adapters=args.lora_max_adapters,
                    lora_rank=args.lora_rank, adapters=args.adapter,
                    qos_rate=args.qos_rate, qos_burst=args.qos_burst,
                    qos_tenant_cap=args.qos_tenant_cap,
                    qos_weights=qos_weights,
                    kv_host_budget_mb=args.kv_host_budget_mb,
                    max_resident_slots=args.max_resident_slots,
                    trace_ring=args.trace_ring,
                    trace_slow_ms=args.trace_slow_ms, layers=args.layers)

    # Warmup-gated readiness: /readyz answers 503 until the engine's
    # warmup pass has built every jitted program, so an orchestrator that
    # waits for ready before routing guarantees no request ever pays a
    # compile (docs/guides/serving-tuning.md, "cold start"). /healthz is
    # liveness only and is green the moment the socket is up.
    ready = threading.Event()

    # Decode tier: admit prefill-tier handoffs and expose each admitted
    # stream at GET /v1/handoffs/<request_id> (SSE) for the front-end to
    # collect. Streams are parked until claimed; a claim is exclusive.
    handoff_streams = {}
    handoff_lock = threading.Lock()

    # Affinity-sketch gossip is pull-based: every data-plane worker
    # fetches /v1/affinity once per epoch poll, so the cost of serving
    # it scales with fleet-wide worker count x poll rate. A short TTL
    # cache bounds that cost at one sketch build per TTL no matter how
    # many workers poll, and keeps gossip from contending with
    # generation steps on a busy engine. Staleness it adds (<= the TTL)
    # is far inside the one-poll-interval staleness bound routers
    # already tolerate.
    sketch_cache = {"at": 0.0, "body": None}
    sketch_cache_ttl = 0.25
    sketch_lock = threading.Lock()
    transfer_server = None
    if args.role == "decode":
        from dstack_tpu.workloads.kv_transfer import TransferServer

        def _on_handoff(h):
            out = engine.serving.submit_prefilled(h)
            with handoff_lock:
                handoff_streams[h.request_id] = out

        transfer_server = TransferServer(
            "0.0.0.0", args.kv_transfer_port, _on_handoff,
            epoch=engine.serving.handoff_epoch,
        )
        print(f"kv transfer server on :{transfer_server.port}", flush=True)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _trace_identity(self):
            """(traceparent, request_id) for this request: the inbound
            headers when valid, minted otherwise. Computed per call — a
            handler instance has no per-request state to cache in."""
            hdrs = {k.lower(): v for k, v in self.headers.items()}
            return ensure_request_trace({}, hdrs)

        def _send(self, code: int, obj, headers=()) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            tp, req_id = self._trace_identity()
            self.send_header("X-Request-ID", req_id)
            self.send_header("Traceparent", tp)
            for k, v in headers:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send_overloaded(self, e: EngineOverloadedError) -> None:
            self._send(
                429,
                {"error": {"message": str(e), "type": "overloaded",
                           "retry_after": e.retry_after}},
                headers=[("Retry-After", str(int(e.retry_after + 0.5) or 1))],
            )

        def _send_shed(self, e: TenantShedError) -> None:
            engine.record_tenant(e.tenant, shed=True)
            self._send(
                429,
                {"error": {"message": str(e), "type": "rate_limited",
                           "tenant": e.tenant,
                           "retry_after": e.retry_after}},
                headers=[("Retry-After", str(max(1, int(e.retry_after + 0.5))))],
            )

        def _request_identity(self, req):
            """(adapter, tenant) for this request: the OpenAI `model`
            field selects the adapter (`base:adapter`); tenancy is the
            API key when one was sent, else the adapter name, else the
            shared default bucket — the same identity the engine's
            prefix cache namespaces KV by."""
            model = req.get("model") or ""
            adapter = None
            if ":" in model:
                adapter = model.split(":", 1)[1] or None
            auth = self.headers.get("Authorization", "")
            tenant = None
            if auth.lower().startswith("bearer "):
                tenant = auth[7:].strip() or None
            return adapter, tenant or adapter or DEFAULT_TENANT

        def _stream(self, req) -> None:
            """OpenAI-style SSE: one delta chunk per generated token."""
            # Pull the first piece BEFORE committing the 200/SSE headers, so
            # submit-time errors surface as a clean JSON 500 instead of a
            # second status line spliced into the event stream.
            adapter, tenant = self._request_identity(req)
            tp, req_id = self._trace_identity()
            try:
                pieces = engine.chat_stream(
                    req.get("messages", []), req.get("max_tokens"),
                    req.get("temperature"), req.get("top_p"), req.get("stop"),
                    adapter=adapter, tenant=tenant,
                    traceparent=tp, x_request_id=req_id,
                )
                first = next(pieces)
            except StopIteration:
                first = ""
            except TenantShedError as e:
                return self._send_shed(e)
            except EngineOverloadedError as e:
                engine.record_tenant(tenant, shed=True)
                return self._send_overloaded(e)
            except KeyError as e:  # unknown adapter
                return self._send(404, {"error": f"unknown adapter: {e}"})
            except ValueError as e:  # bad request field (e.g. temperature)
                return self._send(400, {"error": str(e)})
            except Exception as e:
                return self._send(500, {"error": str(e)})
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("X-Request-ID", req_id)
            self.send_header("Traceparent", tp)
            self.end_headers()
            try:
                self._stream_body(first, pieces, req_id)
            except Exception:
                # Headers are committed: a 500 here would splice a second
                # status line into the event stream. Truncating WITHOUT the
                # [DONE] terminator is the SSE convention for "broken".
                return

        def _stream_body(self, first, pieces, req_id=None) -> None:
            for i, piece in enumerate(itertools.chain([first], pieces)):
                chunk = {
                    "id": "chatcmpl-native",
                    "object": "chat.completion.chunk",
                    "created": int(time.time()),
                    "model": args.model_name,
                    "choices": [{
                        "index": 0,
                        "delta": {"content": piece} if i else
                                 {"role": "assistant", "content": piece},
                        "finish_reason": None,
                    }],
                }
                self.wfile.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
                self.wfile.flush()
            # Final usage-style block: the flight recorder's phase summary
            # for this stream, so the client sees where its latency went
            # without a second round trip to the trace endpoint.
            trace = (engine.serving.request_trace(req_id)
                     if req_id is not None else None)
            if trace is not None:
                summary = {
                    "id": "chatcmpl-native",
                    "object": "chat.completion.chunk",
                    "created": int(time.time()),
                    "model": args.model_name,
                    # An empty-delta choice rather than `"choices": []`:
                    # clients that index choices[0] unconditionally (the
                    # common SSE-consumer shape) must survive this chunk.
                    "choices": [{"index": 0, "delta": {},
                                 "finish_reason": None}],
                    "phase_summary": {
                        "request_id": trace["request_id"],
                        "trace_id": trace["trace_id"],
                        "total_seconds": trace["total_seconds"],
                        "phases": trace["phases"],
                        "counters": trace["counters"],
                    },
                }
                self.wfile.write(
                    b"data: " + json.dumps(summary).encode() + b"\n\n"
                )
                self.wfile.flush()
            self.wfile.write(b"data: [DONE]\n\n")

        def do_GET(self):
            if self.path.rstrip("/") == "/healthz":
                return self._send(200, {"ok": True})
            if self.path.rstrip("/") == "/readyz":
                if ready.is_set():
                    stats = engine.serving.stats()
                    return self._send(200, {
                        "ready": True,
                        "warmup_seconds": stats.get("warmup_seconds"),
                        "weights_seconds": round(engine.weights_seconds, 3),
                        "weights_via": engine.weights_via,
                    })
                return self._send(
                    503,
                    {"ready": False, "phase": "warmup"},
                    headers=[("Retry-After", "2")],
                )
            if self.path.rstrip("/") == "/v1/models":
                # Loaded adapters list as models in their own right
                # (`base:adapter`), mirroring the control-plane proxy's
                # routing-cache expansion.
                data = [{"id": args.model_name, "object": "model",
                         "created": 0, "owned_by": "dstack-tpu"}]
                if engine.serving.lora_enabled:
                    for name in sorted(engine.serving.adapters()):
                        data.append({
                            "id": f"{args.model_name}:{name}",
                            "object": "model", "created": 0,
                            "owned_by": "dstack-tpu",
                        })
                return self._send(200, {"object": "list", "data": data})
            path, _, query = self.path.partition("?")
            if path.rstrip("/") == "/v1/affinity":
                # Cache-affinity sketch for fleet routing: resident
                # prefix chain-head digests + loaded adapters, plus the
                # tokenizer parameters a router needs to recompute the
                # SAME chain keys over the SAME block boundaries
                # (tokenizer-consistency is what makes the scores mean
                # "expected matched blocks"). Cheap: no device work,
                # one pass over the host-side cache index, served from a
                # short TTL cache so N polling workers cost one build.
                with sketch_lock:
                    now = time.monotonic()
                    if (sketch_cache["body"] is None
                            or now - sketch_cache["at"] > sketch_cache_ttl):
                        sketch_cache["body"] = {
                            **engine.serving.affinity_sketch(),
                            "model": args.model_name,
                            "tokenizer": {
                                "kind": "byte",
                                "vocab_size": engine.config.vocab_size,
                                "prompt_limit": (
                                    engine.config.max_seq_len
                                    - engine.max_new_tokens
                                ),
                                "min_bucket": Engine.MIN_BUCKET,
                            },
                        }
                        sketch_cache["at"] = now
                    body = sketch_cache["body"]
                return self._send(200, body)
            if path.rstrip("/") == "/metrics":
                # Queue depth, shed counters, and paged-KV pool gauges
                # for scrapers and the control plane's autoscaler
                # signals. JSON by default (existing consumers);
                # Prometheus text when the scraper asks for it via
                # Accept or ?format=prometheus.
                stats = engine.serving.stats()
                accept = self.headers.get("Accept", "")
                if "format=prometheus" in query or "text/plain" in accept:
                    text = prometheus_metrics(stats)
                    tenant_lines = engine.tenant_metrics_lines()
                    if tenant_lines:
                        text = text.rstrip("\n") + "\n" + \
                            "\n".join(tenant_lines) + "\n"
                    body = text.encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if engine.qos is not None:
                    stats = {**stats, "qos": engine.qos.stats()}
                return self._send(200, stats)
            if path.rstrip("/").startswith("/v1/handoffs/"):
                return self._stream_handoff(path.rstrip("/"))
            clean = path.rstrip("/")
            if clean.startswith("/v1/requests/") and clean.endswith("/trace"):
                # Phase timeline by engine request id or client
                # X-Request-ID (live ring first, then the tail store).
                rid = clean[len("/v1/requests/"):-len("/trace")]
                trace = engine.serving.request_trace(rid)
                if trace is None:
                    return self._send(
                        404, {"error": f"no trace for request {rid!r}"}
                    )
                return self._send(200, trace)
            self._send(404, {"error": "not found"})

        def _stream_handoff(self, path: str) -> None:
            """Decode tier: stream a handed-off request's tokens (SSE).

            The claim is exclusive — the queue is popped so two readers
            cannot interleave one stream."""
            try:
                rid = int(path.rsplit("/", 1)[1])
            except ValueError:
                return self._send(400, {"error": "handoff id must be int"})
            with handoff_lock:
                out = handoff_streams.pop(rid, None)
            if out is None:
                return self._send(404, {"error": f"no handoff {rid}"})
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()
            try:
                while True:
                    tok = out.get()
                    if tok is None:
                        self.wfile.write(b"data: [DONE]\n\n")
                        return
                    if isinstance(tok, BaseException):
                        return  # truncate without [DONE]: SSE "broken"
                    ev = {"id": rid, "token": int(tok),
                          "text": engine.decode([tok])}
                    self.wfile.write(
                        b"data: " + json.dumps(ev).encode() + b"\n\n"
                    )
                    self.wfile.flush()
            except OSError:
                engine.serving.cancel(out)  # reader gone: free the slot

        def _load_adapter_route(self) -> None:
            """POST /v1/adapters {"name", "path", "alpha"?}: runtime
            adapter load/replace. 409 when pool slots are all pinned by
            in-flight requests (retryable); 400 on shape/rank mismatch."""
            length = int(self.headers.get("Content-Length", 0))
            try:
                req = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                return self._send(400, {"error": f"bad json: {e}"})
            name, path = req.get("name"), req.get("path")
            if not name or not path:
                return self._send(
                    400, {"error": "`name` and `path` are required"}
                )
            try:
                slot = engine.load_adapter(
                    name, path, alpha=float(req.get("alpha", 16.0))
                )
            except (AdapterPoolFullError, AdapterBusyError) as e:
                return self._send(409, {"error": str(e)})
            except (ValueError, FileNotFoundError) as e:
                return self._send(400, {"error": str(e)})
            except RuntimeError as e:  # engine built without LoRA
                return self._send(400, {"error": str(e)})
            self._send(200, {"name": name, "slot": slot,
                             "model": f"{args.model_name}:{name}"})

        def do_DELETE(self):
            path = self.path.rstrip("/")
            prefix = "/v1/adapters/"
            if not path.startswith(prefix):
                return self._send(404, {"error": "not found"})
            name = path[len(prefix):]
            try:
                engine.serving.unload_adapter(name)
            except AdapterBusyError as e:
                return self._send(409, {"error": str(e)})
            except KeyError:
                return self._send(404, {"error": f"unknown adapter: {name}"})
            except RuntimeError as e:
                return self._send(400, {"error": str(e)})
            self._send(200, {"name": name, "unloaded": True})

        def do_POST(self):
            path = self.path.rstrip("/")
            if path == "/v1/adapters":
                return self._load_adapter_route()
            if path != "/v1/chat/completions":
                return self._send(404, {"error": "not found"})
            length = int(self.headers.get("Content-Length", 0))
            tenant = DEFAULT_TENANT
            try:
                req = json.loads(self.rfile.read(length) or b"{}")
                if req.get("stream"):
                    return self._stream(req)
                adapter, tenant = self._request_identity(req)
                tp, req_id = self._trace_identity()
                usage = {}
                text = engine.chat(req.get("messages", []),
                                   req.get("max_tokens"), req.get("temperature"),
                                   req.get("top_p"), req.get("stop"),
                                   usage_out=usage,
                                   adapter=adapter, tenant=tenant,
                                   traceparent=tp, x_request_id=req_id)
            except TenantShedError as e:
                return self._send_shed(e)
            except EngineOverloadedError as e:
                engine.record_tenant(tenant, shed=True)
                return self._send_overloaded(e)
            except KeyError as e:  # unknown adapter
                return self._send(404, {"error": f"unknown adapter: {e}"})
            except ValueError as e:  # bad request field (e.g. temperature)
                return self._send(400, {"error": str(e)})
            except Exception as e:  # surface engine errors as API errors
                return self._send(500, {"error": str(e)})
            finish = usage.pop("finish_reason", "length")
            handoff_id = usage.pop("handoff_id", None)
            self._send(200, {
                "id": "chatcmpl-native",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": args.model_name,
                "choices": [{
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish,
                }],
                "usage": {**usage,
                          "total_tokens": sum(usage.values())} if usage else {},
                **({"handoff_id": handoff_id}
                   if handoff_id is not None else {}),
            })

    class ModelHTTPServer(ThreadingHTTPServer):
        # Accept backlog deeper than BaseServer's 5: bursts must reach
        # admission control and get a 429 + Retry-After, not a
        # kernel-level connection refusal indistinguishable from an
        # outage. Subclassed so the stdlib class is not mutated.
        request_queue_size = 64

    server = ModelHTTPServer(("0.0.0.0", args.port), Handler)
    print(f"native model server: {args.model_name} on :{args.port}", flush=True)
    if args.no_warmup:
        ready.set()
    else:
        # Warm in the background so /healthz (and early traffic, which
        # simply pays its own compiles) answer while programs build;
        # /readyz flips only after warmup_end.
        def _warm() -> None:
            try:
                r = engine.serving.warmup()
            except EngineBusyError as e:
                # A request raced admission before warmup started (the
                # idle-check refused). Readiness still flips — the racer
                # is paying the compiles warmup would have.
                print(f"warmup skipped: {e}", flush=True)
            except Exception:
                # A program that did not build (lowering error, Mosaic
                # refusal, VMEM limit, OOM): a server that cannot decode
                # must not stay up answering /healthz 200 and /readyz 503
                # forever, let alone turn ready. This is a daemon thread,
                # so raising would only end the thread — end the process.
                traceback.print_exc()
                print("warmup failed: exiting", file=sys.stderr, flush=True)
                os._exit(1)
            else:
                print(
                    f"warmup: {r['programs']} programs in"
                    f" {r['seconds']:.2f}s ({r['compiles']} built,"
                    f" {r['cache_hits']} from persistent cache)",
                    flush=True,
                )
            ready.set()

        threading.Thread(target=_warm, daemon=True, name="warmup").start()
    server.serve_forever()


if __name__ == "__main__":
    main()
