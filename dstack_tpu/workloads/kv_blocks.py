"""Paged KV cache: block pool + prefix sharing + chunked prefill.

A dense serving layout (one `(max_len, KV, hd)` strip per slot) wastes
HBM twice: a short request reserves the whole strip, and N requests that
share a system prompt hold N copies of its KV. This module keeps a
vLLM-style *block pool* instead — `k`/`v` are
`(L, num_blocks, block_size, KV, hd)` and each slot owns an int32 *block
table* row mapping its logical cache positions to pool blocks — plus:

- a host-side `BlockAllocator` with refcounts and a hash-chained prefix
  cache (full blocks keyed by the sha1 chain of their token contents,
  partial tails keyed by `(parent_hash, tail_tokens)`), so a request
  whose prompt prefix was already prefilled retains the existing blocks
  instead of recomputing them; writers copy-on-write any block they
  share (`ensure_writable`);
- `make_chunk_prefill`: prefill one budget-bounded token chunk of one
  prompt directly into the pool, so a long prompt interleaves with
  decode chunks instead of monopolizing the device;
- `make_paged_decode_step`: the per-token decode body against the pool;
  it, the prefill's first token and speculation's accept test all take
  their sampling law from `sampling.py`.

The reference these programs are held to is `generate.generate` (one
request, one static strip, no paging): tests/test_serving_paged.py and
its siblings pin the engine's temperature-0 token streams to it.

Every attention in this module goes through
`paged_attention.ragged_attention`: it attends STRAIGHT against the
stacked pool at a layer index, through the block tables, with a
streaming softmax that walks one table column (one block) at a time.
No program here materializes a dense `(max_len, ...)` per-slot view:
no whole-pool `jnp.take(pool, block_tables, ...)` gather, no matching
full-view scatter (the static analyzer's KVB01 check keeps them out).
Each program writes only the handful of rows it produced, scattered by
`(layer, block, offset)` before the layer's attention so in-flight rows
see themselves and their predecessors exactly as `generate.generate`'s
cache rows do.
The pool rides every program's layer loop as a CARRY (`_layer_loop`),
so those scatters update it in place; it is never an `xs`/`ys` of the
layer scan, which would copy each layer's slab out and back per
layer-step and the whole pool once per decode step.

Correctness leans on two XLA facts (pallas_guide: gather/scatter modes):
garbage in unwritten or stale pool blocks is harmless because attention
masks positions `>= valid_len` (and pad-sentinel table entries) *before*
softmax (all pool gathers use `mode="clip"` so padding never introduces
NaN — a NaN value row would survive masking as `0 * NaN`), and all pool
writes use `mode="drop"` with an out-of-bounds sentinel index
(`num_blocks` for blocks, `max_len` for rows) so padded or inactive
lanes simply vanish instead of clobbering block 0.
"""

import functools
import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dstack_tpu.workloads import moe
from dstack_tpu.workloads.config import MAMBA, ModelConfig
from dstack_tpu.workloads.paged_attention import ragged_attention
from dstack_tpu.workloads.sampling import (
    _sampling_probs,
    _select_next_token,
    sample_logits_row,
)
from dstack_tpu.workloads.selective_scan import scan_impl, selective_scan_decode
from dstack_tpu.workloads.transformer import (
    absorb_query,
    attn_output,
    embed_tokens,
    final_norm,
    head_gate,
    head_weights,
    latent_output,
    layer_stacks,
    logits_linear,
    mamba_inputs,
    mamba_mixer,
    mamba_output,
    mixer_stacks,
    mlp_block,
    project_latent,
    project_qkv,
    rms_norm,
    scan_layers,
)

Params = Dict[str, Any]


class PagedDecodeState(NamedTuple):
    """Block-pool decode state. The sampling law (sampling.py) reads the
    per-slot `active`, `temperature` and `top_p` rows by name."""

    # The pools' trailing (heads, width) is ModelConfig.kv_row_shapes():
    # (KV, hd) twice for GQA; ONE latent pool (1, row) and a zero-wide v
    # (no bytes, never read) for latent attention.
    # Their layer axis counts the layers that keep rows (every layer, but
    # for a model with state-space layers: its attention layers only).
    k: jnp.ndarray            # (L, num_blocks, block_size, KV, hd)
    v: jnp.ndarray
    block_tables: jnp.ndarray  # (B, max_blocks) int32; pad = num_blocks
    lengths: jnp.ndarray      # (B,) filled cache positions
    last_token: jnp.ndarray   # (B,) next token to feed
    active: jnp.ndarray       # (B,) bool
    remaining: jnp.ndarray    # (B,) new tokens still budgeted
    temperature: jnp.ndarray  # (B,) f32; 0 = greedy
    top_p: jnp.ndarray        # (B,) f32; 1 = no filtering
    adapter_ix: jnp.ndarray   # (B,) int32 LoRA pool slot; -1 = no adapter
    # The recurrent-state pool of a model with state-space layers, a slot a
    # row whatever its context (ModelConfig.state_shapes): the scan's state
    # and the convolution's tail. None (no leaf, no operand of any program)
    # for every other model.
    # A slot's tail is ONE row of the pool, its d_conv - 1 = 3 inputs side
    # by side: as a second-minor axis the 3 would be padded to a tile of
    # 16, and XLA re-laid the whole pool out at each end of a chunk's
    # layer loop (AOT for the v5e, PR 33).
    ssm: Optional[jnp.ndarray] = None   # (Ls, B, d_state, d_inner) float32
    conv: Optional[jnp.ndarray] = None  # (Ls, B, (d_conv - 1) * d_inner)
    # Where the expert bank here is a share of the experts routed over
    # (ModelConfig.expert_share): the pairs real tokens were routed to, and
    # those of them that fell on the experts held (moe.local_pairs), summed
    # over layers and programs since a decode launch last handed them out
    # (it returns them beside its tokens and starts again from zero). None
    # for every other model, as above.
    moe_pairs: Optional[jnp.ndarray] = None  # (2,) int32


def init_paged_state(
    config: ModelConfig,
    batch: int,
    max_len: int,
    block_size: int,
    num_blocks: int,
    state_dtype=jnp.float32,
) -> PagedDecodeState:
    """`state_dtype` is the scan state's: float32, but for the test that
    shows what bfloat16 costs (tests/test_state_space_model.py)."""
    c = config
    if max_len % block_size != 0:
        raise ValueError(
            f"kv_block_size {block_size} must divide max_len {max_len}"
        )
    max_blocks = max_len // block_size
    k_row, v_row = c.kv_row_shapes()
    shape = (c.n_attn_layers, num_blocks, block_size)
    recurrent = {}
    if c.has_state_layers:
        h_row, (taps, width) = c.state_shapes()
        recurrent = {
            "ssm": jnp.zeros((c.n_state_layers, batch) + h_row, state_dtype),
            "conv": jnp.zeros(
                (c.n_state_layers, batch, taps * width), c.activation_dtype),
        }
    if c.expert_share:
        recurrent["moe_pairs"] = jnp.zeros((2,), jnp.int32)
    return PagedDecodeState(
        **recurrent,
        k=jnp.zeros(shape + k_row, c.activation_dtype),
        v=jnp.zeros(shape + v_row, c.activation_dtype),
        block_tables=jnp.full((batch, max_blocks), num_blocks, jnp.int32),
        lengths=jnp.zeros((batch,), jnp.int32),
        last_token=jnp.zeros((batch,), jnp.int32),
        active=jnp.zeros((batch,), bool),
        remaining=jnp.zeros((batch,), jnp.int32),
        temperature=jnp.zeros((batch,), jnp.float32),
        top_p=jnp.ones((batch,), jnp.float32),
        adapter_ix=jnp.full((batch,), -1, jnp.int32),
    )


# -- host-side allocator ------------------------------------------------------


def _chain_hash(parent: bytes, block_tokens) -> bytes:
    """sha1 chain over block contents: a block's key commits to every
    token before it, so equal hashes mean equal logical prefixes."""
    return hashlib.sha1(parent + repr(tuple(block_tokens)).encode()).digest()


class BlockAllocator:
    """Refcounted free-list over the pool + LRU prefix cache.

    NOT thread-safe — the engine serializes calls under its own lock.
    Refcount convention: `_ref[b]` counts holders (one per task/slot
    table referencing b, plus one if the prefix cache retains it). A
    block leaves the free list only via `alloc()` and returns only when
    its refcount hits zero; cached blocks therefore never free until
    evicted. Cache keys: `("F", h)` for a full block (h = chain hash
    through that block), `("P", h, tail_tokens)` for a partial tail
    whose parent chain is h. Evicting a parent leaves children
    unreachable (the match walk stops at the gap); they age out via LRU.

    Multi-tenancy: `match`/`insert_full`/`insert_tail` take a `namespace`
    (adapter identity). A non-empty namespace seeds the hash chain, so
    two tenants with byte-identical prompts but different adapters can
    NEVER share a prefix block — an adapter changes the KV contents, and
    a cross-tenant hit would serve tenant A's attention over tenant B's
    cache (poisoning). Same-namespace re-runs still hit normally.

    Host tier (optional): `spill(key, block)` is called at the eviction
    seam in `alloc()` while the victim block's device contents are still
    intact, so the owner can ship the KV payload to host memory before
    the block is recycled. `swap_in(key) -> Optional[block]` is called
    on a cache miss in `match()`: the owner pulls the payload back from
    the host tier into a freshly allocated device block and returns it
    (with ref=1, which becomes the cache's hold), or None when the
    payload isn't spilled / no device block frees up. Both hooks may
    reenter `alloc()` (a swap-in can itself trigger a spill); they never
    reenter `match()`.
    """

    def __init__(self, num_blocks: int, block_size: int, cache: bool = True,
                 spill=None, swap_in=None):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.cache_enabled = cache
        self._spill = spill
        self._swap_in = swap_in
        self._free: List[int] = list(range(num_blocks))
        self._ref = [0] * num_blocks
        self._cache: "OrderedDict[tuple, int]" = OrderedDict()
        self._block_key: Dict[int, tuple] = {}
        self.hits = 0
        self.misses = 0
        self.host_hits = 0       # matches that pulled >=1 block from host
        self.tokens_reused = 0
        self.cow_copies = 0
        self.evictions = 0
        self._last_lookup_swapped = False

    @property
    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    @property
    def cached(self) -> int:
        return len(self._cache)

    def alloc(self) -> Optional[int]:
        """Pop a free block (ref=1), evicting the LRU cache entry whose
        block is solely cache-held if that's what it takes; None when
        every block is pinned by a live table. Entries for table-held
        blocks are deliberately NOT dropped — they cost nothing now and
        can still serve matches (or free later when the table retires)."""
        if not self._free:
            victim = next((k for k, b in self._cache.items()
                           if self._ref[b] == 1), None)
            if victim is None:
                return None
            b = self._cache.pop(victim)
            del self._block_key[b]
            self.evictions += 1
            if self._spill is not None:
                # Device contents are still intact here — nothing has
                # written to block b since the cache published it.
                self._spill(victim, b)
            self._ref[b] -= 1
            self._free.append(b)
        b = self._free.pop()
        self._ref[b] = 1
        return b

    def release(self, b: int) -> None:
        self._ref[b] -= 1
        assert self._ref[b] >= 0, f"double release of block {b}"
        if self._ref[b] == 0:
            self._free.append(b)

    def retain(self, b: int) -> None:
        self._ref[b] += 1

    def ensure_writable(self, b: int) -> Tuple[Optional[int], bool]:
        """(block, needs_copy): a privately held block is returned as-is;
        a shared one is swapped for a fresh allocation the caller must
        copy-on-write into (our share of the old block is released)."""
        if self._ref[b] <= 1:
            return b, False
        nb = self.alloc()
        if nb is None:
            return None, False
        self._ref[b] -= 1
        self.cow_copies += 1
        return nb, True

    def match(
        self, tokens: List[int], namespace: bytes = b""
    ) -> Tuple[List[int], int]:
        """Longest cached prefix of `tokens`: full blocks down the hash
        chain, then the longest partial tail. Matched blocks are
        RETAINED for the caller (released like any table block). At
        least one trailing token is always left uncovered — the prefill
        must compute the last prompt position's logits to sample the
        first token."""
        if not self.cache_enabled:
            return [], 0
        bs = self.block_size
        limit = len(tokens) - 1
        blocks: List[int] = []
        h = self._ns_seed(namespace)
        matched = 0
        swapped_in = False
        while (len(blocks) + 1) * bs <= limit:
            h2 = _chain_hash(h, tokens[matched:matched + bs])
            b = self._lookup(("F", h2))
            if b is None:
                break
            swapped_in = swapped_in or self._last_lookup_swapped
            self._ref[b] += 1
            blocks.append(b)
            matched += bs
            h = h2
        for f in range(min(limit - matched, bs - 1), 0, -1):
            key = ("P", h, tuple(tokens[matched:matched + f]))
            b = self._lookup(key)
            if b is not None:
                swapped_in = swapped_in or self._last_lookup_swapped
                self._ref[b] += 1
                blocks.append(b)
                matched += f
                break
        if matched:
            self.hits += 1
            if swapped_in:
                self.host_hits += 1
        else:
            self.misses += 1
        self.tokens_reused += matched
        return blocks, matched

    def _lookup(self, key: tuple) -> Optional[int]:
        """Cache probe with host-tier fallback: a device hit bumps LRU;
        a miss asks `swap_in` to resurrect the block from host memory
        and republishes it under `key` (the swap-in's ref=1 becomes the
        cache's hold)."""
        self._last_lookup_swapped = False
        b = self._cache.get(key)
        if b is not None:
            self._cache.move_to_end(key)
            return b
        if self._swap_in is None:
            return None
        b = self._swap_in(key)
        if b is None:
            return None
        self._cache[key] = b
        self._block_key[b] = key
        self._last_lookup_swapped = True
        return b

    @staticmethod
    def _ns_seed(namespace: bytes) -> bytes:
        """Chain seed for a tenant namespace. Hashed (not raw) so a crafted
        adapter name can't alias another namespace's 20-byte chain digest;
        empty namespace keeps the legacy un-namespaced chain."""
        if not namespace:
            return b""
        return hashlib.sha1(b"ns:" + namespace).digest()

    def insert_full(
        self, tokens: List[int], table: List[int], namespace: bytes = b""
    ) -> None:
        """Publish every complete prompt block of a finalized prefill.
        Called at finalize DISPATCH time: device program order guarantees
        the chunk writes complete before any later matcher's gather runs,
        so publishing early is safe and maximizes burst hit rate."""
        if not self.cache_enabled:
            return
        bs = self.block_size
        h = self._ns_seed(namespace)
        for i in range(len(tokens) // bs):
            h = _chain_hash(h, tokens[i * bs:(i + 1) * bs])
            key = ("F", h)
            if key in self._cache:
                self._cache.move_to_end(key)
                continue
            if i >= len(table) or table[i] in self._block_key:
                continue
            b = table[i]
            self._cache[key] = b
            self._block_key[b] = key
            self._ref[b] += 1

    def insert_tail(
        self, tokens: List[int], table: List[int], namespace: bytes = b""
    ) -> None:
        """Publish the partial-tail prompt block at RETIRE time (no live
        writer left). The block also holds this request's decode KV past
        the tail — harmless: a matcher's valid region ends at the tail,
        and attention masks everything beyond it."""
        if not self.cache_enabled:
            return
        bs = self.block_size
        nfull = len(tokens) // bs
        f = len(tokens) - nfull * bs
        if f == 0 or nfull >= len(table):
            return
        h = self._ns_seed(namespace)
        for i in range(nfull):
            h = _chain_hash(h, tokens[i * bs:(i + 1) * bs])
        key = ("P", h, tuple(tokens[nfull * bs:]))
        if key in self._cache or table[nfull] in self._block_key:
            return
        b = table[nfull]
        self._cache[key] = b
        self._block_key[b] = key
        self._ref[b] += 1

    def drop_cache(self) -> int:
        """Forget every cached prefix entry (the cached KV became invalid
        wholesale — e.g. a weight refresh: old-policy keys/values must
        never graft under new params). Cache-only holds return to the
        free list; table-held blocks just lose their cache entry and
        free when the table retires. Nothing is spilled — KV that no
        longer matches the model is not worth host RAM either. Returns
        the number of entries dropped."""
        n = len(self._cache)
        for b in self._cache.values():
            del self._block_key[b]
            self.release(b)
        self._cache.clear()
        return n

    # Affinity-sketch digest width: 16 hex chars (64 bits) of the sha1
    # chain hash — far beyond collision range for the few hundred
    # resident blocks a sketch carries, at a fifth of the wire size.
    DIGEST_HEX = 16

    def affinity_digests(self, limit: int = 512) -> List[str]:
        """Resident full-block chain-head digests for the routing
        affinity sketch, most-recently-used last, bounded to the `limit`
        hottest entries (OrderedDict insertion/move order IS the LRU
        order). Partial-tail entries are excluded — a router cannot
        reconstruct their tail-token keys, and a tail never anchors a
        longer chain anyway. Digests already commit to the tenant
        namespace (insert_full seeds the chain with _ns_seed), so a
        sketch can be published without leaking cross-tenant equality:
        equal digests require equal namespace AND equal tokens."""
        digests = [
            key[1].hex()[: self.DIGEST_HEX]
            for key in self._cache
            if key[0] == "F"
        ]
        return digests[-limit:]

    def stats(self) -> Dict[str, int]:
        return {
            "blocks_total": self.num_blocks,
            "blocks_in_use": self.in_use,
            "blocks_cached": self.cached,
            "hits": self.hits,
            "misses": self.misses,
            "host_hits": self.host_hits,
            "tokens_reused": self.tokens_reused,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
        }


# -- jitted programs ----------------------------------------------------------
#
# Every factory takes an optional `shardings` (a `sharding.ServingShardings`):
# when set, the program is jitted with explicit in/out shardings — params
# column-parallel over "model", KV pools sharded on the KV-head dim, control
# state replicated — and GSPMD partitions the SAME traced logic; there are no
# sharded/unsharded code forks. When None (the default), jit behaves as before.
#
# `attn_impl` names the ragged-attention implementation the program traces
# ("pallas" / "lax_ragged"). The engine decides it ONCE from its geometry,
# backend and shard count (paged_attention.dispatch_path) and hands the same
# string to every factory and to its dispatch counter, so the path an engine
# reports is the path its programs traced. None lets `ragged_attention` decide
# from the operand shapes alone (unsharded library callers and tests).


def _extra_leaves(state: PagedDecodeState):
    """The names of the state's leaves beyond the KV pool that
    `_layer_loop` hands back, in its order."""
    if state.ssm is not None:
        return ("ssm", "conv")
    return ("moe_pairs",) if state.moe_pairs is not None else ()


def _jit_shardings(in_shardings, out_shardings):
    if in_shardings is None:
        return {}
    return {"in_shardings": in_shardings, "out_shardings": out_shardings}


def _layer_loop(c: ModelConfig, params, x, positions, k_pool, v_pool,
                blk, off, tables, valid_len, *, bank=None, adapter_ix=None,
                has_lora=None, attn_impl: Optional[str] = None,
                partitioned: bool = False, recurrent=None, tally=None):
    """The layer loop of every paged program -> (x, k_pool, v_pool), with
    `recurrent` -> (x, k_pool, v_pool, ssm, conv), and with `tally` =
    (counted (B, S) bool: the rows' real tokens, the count so far (2,) int32)
    -> (x, k_pool, v_pool, the count with the expert layers'
    `moe.local_pairs` of those tokens added).

    x (B, S, d) at `positions` runs through the layers; layer l writes its new
    K/V rows into the STACKED pool (L, num_blocks, block_size, KV, hd) at `[l,
    blk, off]` (blk/off (B, S); lanes pointed at the sentinel block
    `num_blocks` drop) and then attends raggedly over `tables` with per-row
    `valid_len`, so in-flight rows see themselves and their predecessors. With
    a LoRA `bank` the q/k/v projection adds each request's unmerged delta
    (lora_serving.project_qkv_lora): `adapter_ix` is a scalar for the
    one-request prefill program, (B,) for decode/verify, -1 = none; `has_lora`
    gates the LoRA math. `partitioned` says GSPMD partitions the program over a
    mesh (the factory was given `shardings`): the expert bank is then not whole
    on a device, which `moe.moe_mlp` cannot tell from a traced weight.

    The pool is a carry of the scan, never an `xs`/`ys`: a scan cannot alias
    `xs` to `ys`, so the stacked form sliced each layer's K and V slab out into
    a fresh buffer and wrote it back into a second stack every layer-step, and
    the step loop around it copied the whole pool once per decode step — 64% of
    the chat cell's device time on the v5e (PERF.md §6, PR 26). As a carry the
    two scatters update the donated pool in place and nothing of pool or slab
    shape is moved (tests/test_tpu_lowering.py reads the compiled HLO). Do not
    flatten (L, num_blocks) for the WRITE: sentinel + l * num_blocks would land
    in layer l+1 instead of out of bounds.

    A model with state-space layers hands in `recurrent` = (ssm, conv, slot,
    n_valid (B,), fresh (B,)): the state pool, a carry updated in place like
    the KV pool; which slot x's ONE row is (a prefill chunk), or None where x's
    rows are the pool's slots in order (a decode step); how many of a row's
    tokens are the sequence's (a padded tail and a row that is not live move no
    state); and which rows start a sequence (from zero state, whoever held the
    slot). Its attention layers index the KV pool by their rank among them.
    """
    if recurrent is not None:
        _, _, slot, n_valid, fresh = recurrent

    def rows_of(pool, m, start, shape=None):
        """Layer m of a state pool at x's rows (each of `shape`), 0 at `start`."""
        with jax.named_scope("mamba/state"):
            if slot is None:
                rows = lax.dynamic_index_in_dim(pool, m, keepdims=False)
            else:
                at = (m, slot) + (0,) * (pool.ndim - 2)
                rows = lax.dynamic_slice(pool, at, (1, 1) + pool.shape[2:])[0]
            rows = rows.reshape(rows.shape[:1] + (shape or rows.shape[1:]))
            return jnp.where(start, 0, rows)

    def put_rows(pool, m, rows):
        with jax.named_scope("mamba/state"):
            if slot is None:
                return lax.dynamic_update_index_in_dim(pool, rows, m, 0)
            return lax.dynamic_update_slice(
                pool, rows[None], (m, slot) + (0,) * (pool.ndim - 2))

    def mix(x, p, m, ssm, conv):
        """State-space layer of rank m -> (the mixer's output, the pools)."""
        impl = scan_impl(*ssm.shape[2:])
        start = fresh[:, None, None]
        with jax.named_scope("mamba/proj"):
            x = rms_norm(x, p["attn_norm"], c.norm_eps)
        tail0 = rows_of(conv, m, start, c.state_shapes()[1])
        if slot is None and impl == "pallas" and ssm.dtype == jnp.float32:
            # A decode step on the TPU: the kernel updates the live rows'
            # state in the pool, in place, and touches no other row.
            u, z, delta, b_in, c_out, tail = mamba_inputs(c, x, p, tail0, n_valid)
            with jax.named_scope("mamba/scan"):
                y, ssm = selective_scan_decode(
                    ssm, m, n_valid > 0, delta[:, 0], u[:, 0].astype(jnp.float32),
                    b_in[:, 0], c_out[:, 0], -jnp.exp(p["A_log"]),
                )
            out = mamba_output(x.dtype, y[:, None], u, z, p)
        else:
            out, h, tail = mamba_mixer(
                c, x, p, rows_of(ssm, m, start), tail0, n_valid,
                scan_impl=impl,
            )
            ssm = put_rows(ssm, m, h)
        return out, ssm, put_rows(conv, m, tail.reshape(x.shape[0], -1))

    if bank is None:
        project = lambda x, p, lp, kind: project_qkv(c, x, p, positions, kind)
    else:
        from dstack_tpu.workloads.lora_serving import project_qkv_lora

        pool = bank["scale"].shape[0] - 1            # the all-zero slot
        safe = jnp.where(adapter_ix >= 0, adapter_ix, pool).astype(jnp.int32)
        scale = jnp.take(bank["scale"], safe)
        project = lambda x, p, lp, kind: project_qkv_lora(
            c, x, p, positions, lp, safe, scale, has_lora
        )

    def attend(x, p, lp, l, kp, vp, kind):
        """Write layer l's rows (it is a `kind` layer), attend over the tables
        -> the block's attention output (before the residual) and the pools."""
        if c.latent:
            # One row a token for all heads; the absorbed query scores
            # straight against cached rows and no step up-projects them.
            q, row = project_latent(c, x, p, positions, kp.shape[-1])
            with jax.named_scope("attn/write"):
                kp = kp.at[l, blk, off].set(
                    row[:, :, None].astype(kp.dtype), mode="drop"
                )
            q = absorb_query(c, q, p, kp.shape[-1])
            with jax.named_scope("mla/attend"):
                o_lat = ragged_attention(
                    q, kp, vp, l, tables, valid_len, impl=attn_impl,
                    latent_values=c.kv_lora_rank, scale=c.head_dim ** -0.5,
                )
            return latent_output(c, o_lat, p), kp, vp
        q, k, v = project(x, p, lp, kind)
        with jax.named_scope("attn/write"):
            kp = kp.at[l, blk, off].set(k.astype(kp.dtype), mode="drop")
            vp = vp.at[l, blk, off].set(v.astype(vp.dtype), mode="drop")
        # A window layer writes every row as a full one does: it READS a window.
        window = c.window(kind)
        with jax.named_scope("attn/window" if window else "attn/full"):
            attn = ragged_attention(
                q, kp, vp, l, tables, valid_len, impl=attn_impl,
                window=window,
            )
        return attn_output(attn, p, head_gate(c, x, p)), kp, vp

    tallied = {} if tally is None else {"counted": tally[0]}

    def block(bank_stack, first, carry, layer, kind):
        if recurrent is not None:
            (p, l, lp), (own, rank) = layer
            p = {**p, **own}
            x, kp, vp, ssm, conv = carry
            if kind == MAMBA:
                out, ssm, conv = mix(x, p, rank, ssm, conv)
            else:
                out, kp, vp = attend(x, p, lp, rank, kp, vp, kind)
            return (mlp_block(c, x + out, p), kp, vp, ssm, conv), None
        x, kp, vp, *count = carry
        if isinstance(layer[0], tuple):   # (every layer's, its kind's own)
            layer, (own, _) = layer
            layer = ({**layer[0], **own},) + layer[1:]
        p, l, lp = layer
        out, kp, vp = attend(x, p, lp, l, kp, vp, kind)
        x = x + out
        if bank_stack:
            x, _, *pairs = moe.moe_block(
                c, x, {**p, **bank_stack}, layer=l - first, **tallied)
        elif "router" in p:
            x, _, *pairs = moe.moe_block(
                c, x, p, partitioned=partitioned, **tallied)
        else:
            x, pairs = mlp_block(c, x, p), ()
        if pairs:
            count = [count[0] + pairs[0]]
        return (x, kp, vp, *count), None

    # The pool keeps ONE layer axis over every kind of block: a model's
    # leading dense layers take its first indices, the expert layers the
    # rest, each stack one scan with the pool still the carry. Where the
    # layers are of more than one kind of ATTENTION the scan steps over
    # periods of the pattern (transformer.scan_layers): block j of period
    # t is layer t * p + j, its window and rotary embedding static.
    # Where the expert layers take the routed path (moe.plan: a long
    # chunk), its kernel reads a layer's experts in place in the stacked
    # bank, as the attention kernel reads a layer of the pool: the bank
    # stays out of the scan's `xs`, which would cut each layer's out into
    # a buffer of its own before a kernel may read it.
    # Where the layers differ in their LEAVES (state-space mixers beside
    # attention mixers), each kind's mixers are a stack of their own, read
    # at a layer's rank among its kind; that rank is also the layer's index
    # in the pool of its kind (KV rows, or state). Where only the query
    # heads differ by kind (`heads_by_kind`: wq, wo and the gate a stack a
    # kind and stack of layers), every layer keeps rows and the pool's
    # index is the layer's own.
    carry, first = (x, k_pool, v_pool), 0
    if recurrent is not None:
        carry += recurrent[:2]
    if tally is not None:
        carry += (tally[1],)
    for stack, mixers, kinds in zip(
        layer_stacks(params), mixer_stacks(params), c.stack_kinds
    ):
        own = mixers and {
            kind: (of, jnp.arange(
                jax.tree_util.tree_leaves(of)[0].shape[0], dtype=jnp.int32))
            for kind, of in mixers.items()
        }
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        bank_stack = {}
        if "router" in stack and moe.takes_routed_path(
            c, x.shape[0], x.shape[1], stack, partitioned=partitioned
        ):
            bank_stack = {w: stack[w] for w in ("we_gate", "we_up", "we_down")}
            stack = {w: a for w, a in stack.items() if w not in bank_stack}
        xs = (
            stack,
            jnp.arange(first, first + n, dtype=jnp.int32),
            None if bank is None else bank["layers"],
        )
        carry, _ = scan_layers(
            functools.partial(block, bank_stack, first), carry, xs, kinds,
            own=own,
        )
        first += n
    return carry


def make_chunk_prefill(config: ModelConfig, chunk: int, shardings=None,
                       lora: bool = False, attn_impl: Optional[str] = None):
    """chunk_prefill(params, state, slot, table_row (MB,), tokens (1, C),
    n_valid, start, budget, temp, top_p, rng, finalize) ->
    (state, first_token ()).

    Runs ONE padded chunk (C = `chunk` tokens, first `n_valid` real) of
    one prompt at cache positions [start, start + n_valid) straight into
    the slot's pool blocks. Everything but C is traced, so the compile
    cache holds one entry per pow-2 chunk bucket regardless of prompt
    length, start offset, or sampling params. `first` is only meaningful
    when `finalize` is set (last chunk): it samples the last prompt
    position's logits (`sampling.sample_logits_row`). Finalize
    also flips the slot live on device (lengths/last_token/active/...)
    so no separate insert program is needed.

    With `lora=True` the program takes two trailing args — the request's
    adapter pool slot (scalar int32, -1 = none) and the adapter bank —
    and applies the per-request LoRA delta unmerged inside the qkv
    projection (lora_serving.project_qkv_lora). `lora=False` traces a
    program byte-identical to the pre-multitenant one.
    """
    c = config
    sh = shardings
    kw = _jit_shardings(
        None if sh is None
        else (sh.params, sh.state) + (sh.replicated,) * (12 if lora else 10),
        None if sh is None else (sh.state, sh.replicated),
    )

    def _impl(params, state: PagedDecodeState, slot, table_row,
              tokens, n_valid, start, budget, temp, top_p, rng,
              finalize, aix, bank):
        C = tokens.shape[1]
        bs = state.k.shape[2]
        nb = state.k.shape[1]
        mb = state.block_tables.shape[1]
        offs = jnp.arange(C, dtype=jnp.int32)
        positions = start + offs                     # (C,)
        valid = offs < n_valid                       # (C,)
        # Pool scatter targets; padded lanes -> block nb (drop).
        blk = jnp.take(
            table_row, jnp.clip(positions // bs, 0, mb - 1), mode="clip"
        )
        blk = jnp.where(valid, blk, nb)
        off = positions % bs
        # Row i of the chunk attends cache positions <= start + i.
        valid_len = start + 1 + offs

        x = embed_tokens(params, tokens)             # (1, C, d)
        # Each layer writes the chunk's rows into the pool FIRST, then
        # attends raggedly over the slot's blocks: row i sees cache
        # positions <= start + i, including the rows just written.
        # Padded lanes hit the sentinel block and drop; valid_len masks
        # whatever garbage their attention rows read.
        # A slot's first chunk starts from zero state inside this program,
        # whoever held the slot before; the recurrence stops at n_valid.
        recurrent = None if state.ssm is None else (
            state.ssm, state.conv, slot, n_valid[None],
            ((start == 0) & (n_valid > 0))[None],
        )
        x, new_k, new_v, *new_recurrent = _layer_loop(
            c, params, x, positions, state.k, state.v,
            blk[None], off[None], table_row[None], valid_len[None],
            bank=bank, adapter_ix=aix, has_lora=aix >= 0,
            attn_impl=attn_impl, partitioned=shardings is not None,
            recurrent=recurrent,
            tally=None if state.moe_pairs is None
            else (valid[None], state.moe_pairs),
        )
        h = final_norm(c, params, x)
        h_last = jnp.take(
            h[0], jnp.clip(n_valid - 1, 0, C - 1), axis=0, mode="clip"
        )
        logits = logits_linear(h_last[None], head_weights(params))[0]
        first = sample_logits_row(logits, temp, top_p, rng)

        B = state.lengths.shape[0]
        sel = (jnp.arange(B, dtype=jnp.int32) == slot) & finalize
        prompt_len = start + n_valid
        new_state = PagedDecodeState(
            k=new_k,
            v=new_v,
            block_tables=state.block_tables.at[slot].set(table_row),
            lengths=jnp.where(sel, prompt_len, state.lengths),
            last_token=jnp.where(sel, first, state.last_token),
            active=jnp.where(sel, budget > 1, state.active),
            remaining=jnp.where(sel, budget - 1, state.remaining),
            temperature=jnp.where(sel, temp, state.temperature),
            top_p=jnp.where(sel, top_p, state.top_p),
            # Finalize claims the slot for this request's adapter; a slot
            # reused by an adapter-free request resets to -1 here.
            adapter_ix=jnp.where(sel, aix, state.adapter_ix),
            **dict(zip(_extra_leaves(state), new_recurrent)),
        )
        return new_state, first

    if lora:
        @functools.partial(jax.jit, donate_argnums=1, **kw)
        def chunk_prefill_lora(params, state: PagedDecodeState, slot,
                               table_row, tokens, n_valid, start, budget,
                               temp, top_p, rng, finalize, adapter_ix,
                               lora_bank):
            return _impl(params, state, slot, table_row, tokens, n_valid,
                         start, budget, temp, top_p, rng, finalize,
                         adapter_ix, lora_bank)

        return chunk_prefill_lora

    @functools.partial(jax.jit, donate_argnums=1, **kw)
    def chunk_prefill(params, state: PagedDecodeState, slot, table_row,
                      tokens, n_valid, start, budget, temp, top_p, rng,
                      finalize):
        return _impl(params, state, slot, table_row, tokens, n_valid,
                     start, budget, temp, top_p, rng, finalize,
                     jnp.int32(-1), None)

    return chunk_prefill


def make_paged_decode_step(config: ModelConfig, steps: int = 1, shardings=None,
                           lora: bool = False,
                           attn_impl: Optional[str] = None):
    """decode_steps(params, state, rng) -> (state, tokens (B, steps),
    active) over a PagedDecodeState: `steps` tokens for every active
    slot per call, so one host sync delivers a chunk of tokens per slot.
    With `lora=True` the program takes a
    trailing adapter-bank arg and each slot gathers its own A/B pair by
    `state.adapter_ix` (lora_serving.project_qkv_lora); a batch with no
    live adapters skips the LoRA math behind one `lax.cond`.

    Each of the `steps` per-token iterations writes the new row's K/V
    straight into the slot's current block — one O(B)-row scatter per
    layer into the carried pool (`_layer_loop`) — and attends raggedly
    over the block tables (`paged_attention.ragged_attention`). There
    is no cached view for boundary events (prefill chunks, CoW copies,
    table growth, spec rounds) to invalidate. Steady-state decode
    touches only the blocks each slot actually owns — true on the chip
    since PR 26 only: until then the layer scan took the pool as `xs` and returned it as
    `ys`, and the traced runs of PR 22 and PR 24 (mistral-7b.chat) show
    every layer-step slicing out and writing back the layer's whole K
    and V slab (four ops of 0.44 ms over bf16[4608,16,8,128]) and every
    step copying the whole pool twice (`copy.92/.93`, 5.2 ms each):
    31.5 ms of a 50.7 ms step to write 16 rows.

    Sampling is per SLOT (`sampling._select_next_token`: temperature 0
    = greedy argmax, else categorical at that slot's temperature and
    top_p), so requests with different settings share one decode batch.
    Inactive slots never write: their table rows may be stale (blocks
    freed to the cache or another slot at retire), so their write lane
    is pointed at the OOB sentinel block and dropped.
    """
    c = config

    def one_step(params, state: PagedDecodeState, rng, bank=None):
        nb, bs = state.k.shape[1], state.k.shape[2]
        B, mb = state.block_tables.shape
        ml = mb * bs
        positions = state.lengths[:, None]           # (B, 1)
        x = embed_tokens(params, state.last_token[:, None])
        write_ok = state.active & (state.lengths < ml)
        blk = jnp.take_along_axis(
            state.block_tables,
            jnp.clip(state.lengths[:, None] // bs, 0, mb - 1), axis=1,
        )[:, 0]
        blk = jnp.where(write_ok, blk, nb)
        off = state.lengths % bs
        # A retired slot keeps its last length and table on the device:
        # it sees nothing, so attention walks none of its stale blocks.
        valid_len = jnp.where(
            state.active, state.lengths + 1, 0
        )[:, None]                                   # (B, 1)

        aix = state.adapter_ix
        # Live slots only: a row that is not active (empty, or between two
        # of its prefill chunks) keeps its state bit for bit.
        recurrent = None if state.ssm is None else (
            state.ssm, state.conv, None, state.active.astype(jnp.int32),
            jnp.zeros((B,), bool),
        )
        x, new_k, new_v, *new_recurrent = _layer_loop(
            c, params, x, positions, state.k, state.v,
            blk[:, None], off[:, None], state.block_tables, valid_len,
            bank=bank, adapter_ix=aix,
            has_lora=jnp.any(state.active & (aix >= 0)),
            attn_impl=attn_impl, partitioned=shardings is not None,
            recurrent=recurrent,
            tally=None if state.moe_pairs is None
            else (state.active[:, None], state.moe_pairs),
        )
        h = final_norm(c, params, x)
        logits = logits_linear(h[:, -1], head_weights(params))
        next_token = _select_next_token(state, logits, rng)

        act = state.active
        remaining = state.remaining - act.astype(jnp.int32)
        new_active = act & (remaining > 0) & (state.lengths + 2 <= ml)
        new_state = PagedDecodeState(
            k=new_k,
            v=new_v,
            block_tables=state.block_tables,
            lengths=state.lengths + act.astype(jnp.int32),
            last_token=jnp.where(act, next_token, state.last_token),
            active=new_active,
            remaining=remaining,
            temperature=state.temperature,
            top_p=state.top_p,
            adapter_ix=state.adapter_ix,
            **dict(zip(_extra_leaves(state), new_recurrent)),
        )
        return new_state, jnp.where(act, next_token, -1), new_active

    sh = shardings
    kw = _jit_shardings(
        None if sh is None
        else (sh.params, sh.state, sh.replicated)
        + ((sh.replicated,) if lora else ()),
        None if sh is None else (sh.state, sh.replicated, sh.replicated),
    )

    if lora:
        @functools.partial(jax.jit, donate_argnums=1, **kw)
        def decode_steps_lora(params, state: PagedDecodeState, rng, lora_bank):
            def body(carry, step_rng):
                st, _ = carry
                st, toks, active = one_step(params, st, step_rng, lora_bank)
                return (st, active), toks

            (state, active), toks = lax.scan(
                body, (state, state.active), jax.random.split(rng, steps)
            )
            return state, toks.T, active

        return decode_steps_lora

    @functools.partial(jax.jit, donate_argnums=1, **kw)
    def decode_steps(params, state: PagedDecodeState, rng):
        def body(carry, step_rng):
            st, _ = carry
            st, toks, active = one_step(params, st, step_rng)
            return (st, active), toks

        (state, active), toks = lax.scan(
            body, (state, state.active), jax.random.split(rng, steps)
        )
        if state.moe_pairs is None:
            return state, toks.T, active
        # The counts go out with the tokens, read at the sync that reads
        # those, and the state starts again from zero: the programs count
        # in int32, the engine sums in Python's integers.
        return (
            state._replace(moe_pairs=jnp.zeros_like(state.moe_pairs)),
            toks.T, active, state.moe_pairs,
        )

    return decode_steps


# -- speculative decoding (draft k cheap tokens, verify in one forward) -------


def make_spec_draft(config: ModelConfig, k: int, shardings=None,
                    attn_impl: Optional[str] = None):
    """spec_draft(params, draft_k, draft_v, block_tables, lengths,
    last_token, active, temps, top_ps, rng) ->
    (draft_k', draft_v', drafts (B, k), qlogits (B, k, V)).

    The drafter's half of a speculation round: run k+1 single-token
    drafter steps against the DRAFTER pool (same block tables as the
    target — the two pools are indexed by one allocator, so prefix
    sharing and CoW decisions apply to both), each step writing its row
    straight into the pool (the window rows lengths..lengths+k were
    privatized by the engine's `_ensure_spec_writable` before dispatch)
    and attending raggedly over the tables. Step i feeds the previous
    token at position lengths+i and proposes the next, so steps 0..k-1
    yield drafts d_1..d_k; step k's sampled token is discarded but its
    KV write (row lengths+k, the KV of d_k) is what lets a fully
    accepted round continue without a catch-up pass — the drafter's
    valid rows always cover the target's new length, for ANY acceptance
    count.

    `qlogits` are the drafter's logits behind each draft: the verifier
    recomputes q(:) from them with the same `_sampling_probs` so the
    accept test u < p/q and the residual distribution max(p-q, 0) are
    exact (arXiv:2211.17192). Rows for inactive slots are never
    written (their device table rows may be stale — the blocks could
    have been freed to the cache or another slot at retire): their
    write lane is pointed at the OOB sentinel block and dropped."""
    c = config
    sh = shardings
    kw = _jit_shardings(
        None if sh is None
        else (sh.params, sh.pool, sh.pool) + (sh.replicated,) * 7,
        None if sh is None
        else (sh.pool, sh.pool, sh.replicated, sh.replicated),
    )

    @functools.partial(jax.jit, donate_argnums=(1, 2), **kw)
    def spec_draft(params, draft_k, draft_v, block_tables, lengths,
                   last_token, active, temps, top_ps, rng):
        nb, bs = draft_k.shape[1], draft_k.shape[2]
        B, mb = block_tables.shape
        ml = mb * bs

        def one(carry, step_rng):
            dk, dv, pos, token = carry          # dk/dv: the POOL
            x = embed_tokens(params, token[:, None])
            write_ok = active & (pos < ml)
            blk = jnp.take_along_axis(
                block_tables, jnp.clip(pos[:, None] // bs, 0, mb - 1), axis=1
            )[:, 0]
            blk = jnp.where(write_ok, blk, nb)
            off = pos % bs

            x, dk, dv = _layer_loop(
                c, params, x, pos[:, None], dk, dv,
                blk[:, None], off[:, None], block_tables,
                jnp.where(active, pos + 1, 0)[:, None],  # dead slots: nothing
                attn_impl=attn_impl, partitioned=shardings is not None,
            )
            h = final_norm(c, params, x)
            logits = logits_linear(h[:, -1], head_weights(params))  # (B, V)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            probs = _sampling_probs(logits[:, None], temps, top_ps)[:, 0]
            sampled = jax.random.categorical(
                step_rng, jnp.log(jnp.maximum(probs, 1e-38)), axis=-1
            ).astype(jnp.int32)
            nxt = jnp.where(temps > 0, sampled, greedy)
            return (dk, dv, pos + 1, nxt), (nxt, logits)

        (new_k, new_v, _, _), (toks, qlogits) = lax.scan(
            one, (draft_k, draft_v, lengths, last_token),
            jax.random.split(rng, k + 1)
        )
        drafts = toks[:k].T                         # (B, k): d_1..d_k
        qlogits = jnp.moveaxis(qlogits[:k], 0, 1)   # (B, k, V)
        return new_k, new_v, drafts, qlogits

    return spec_draft


def make_spec_verify(config: ModelConfig, k: int, shardings=None,
                     lora: bool = False, attn_impl: Optional[str] = None):
    """spec_verify(params, state, drafts (B, k), qlogits (B, k, V), rng)
    -> (state', emitted (B, k+1), accepted (B,), active (B,)).

    With `lora=True` the program takes a trailing adapter-bank arg: the
    TARGET applies each slot's LoRA delta (state.adapter_ix) so the
    accept test scores the tenant's actual distribution. The drafter
    stays adapter-free — a base-model drafter only lowers acceptance,
    never correctness (greedy slots accept the leading run matching the
    LoRA'd target argmax; sampling slots rejection-sample against the
    LoRA'd p).

    The target's half of a speculation round, shaped like a chunked
    prefill over every slot at once: feed [last_token, d_1..d_k] at
    positions lengths..lengths+k, write the k+1 rows straight into each
    slot's pool blocks, attend raggedly with per-slot valid lengths,
    and score all k+1 positions in ONE forward — logits[:, j]
    conditions on the drafts up to d_j exactly as the sequential decode
    body would.

    Acceptance per slot: greedy slots (temp 0) accept the leading run
    of drafts matching the target argmax — bit-exact with non-
    speculative decode by construction; sampling slots run rejection
    sampling (accept d_j iff u_j < p_j(d_j) / q_j(d_j), correction
    token from the residual norm(max(p-q, 0)), bonus token from p_k
    when everything accepts), which preserves the target distribution
    exactly. Emission caps (`remaining` budget, cache capacity) and the
    retire conditions replicate `make_paged_decode_step`'s, so a
    speculative slot stops on exactly the token the plain path would have stopped on.

    ROLLBACK IS LENGTH GATING OVER A PRIVATIZED WINDOW: all k+1 rows
    are written to the pool (in-flight rows must be visible to later
    positions' attention), but the engine's `_ensure_spec_writable`
    copy-on-writes every block the window rows lengths..lengths+k
    touch BEFORE each round, so rejected-draft KV lands only in blocks
    this slot holds privately — refcounted / cache-published blocks
    cannot be corrupted by a failed speculation. Lengths advance only
    by the emitted count, so rejected rows sit past valid_len (masked
    by every later attention) until the next round overwrites them.
    `accepted` is the UNCAPPED accepted-draft count m (for the
    engine's acceptance EWMAs); `emitted` rows use the decode path's
    -1 padding convention so the engine's fan-out is shared."""
    c = config
    S = k + 1
    sh = shardings
    kw = _jit_shardings(
        None if sh is None
        else (sh.params, sh.state) + (sh.replicated,) * (4 if lora else 3),
        None if sh is None else (sh.state,) + (sh.replicated,) * 3,
    )

    def _impl(params, state: PagedDecodeState, drafts, qlogits, rng, bank):
        nb, bs = state.k.shape[1], state.k.shape[2]
        B, mb = state.block_tables.shape
        ml = mb * bs
        lens = state.lengths
        act0 = state.active
        offs = jnp.arange(S, dtype=jnp.int32)
        tokens = jnp.concatenate([state.last_token[:, None], drafts], axis=1)
        positions = lens[:, None] + offs[None, :]            # (B, S)
        # Pool targets for the k+1 in-flight rows; inactive slots (their
        # tables may be stale) and rows past the cache -> sentinel, drop.
        ok_w = act0[:, None] & (positions < ml)
        blk = jnp.take_along_axis(
            state.block_tables, jnp.clip(positions // bs, 0, mb - 1), axis=1
        )
        blk = jnp.where(ok_w, blk, nb)
        off = positions % bs

        x = embed_tokens(params, tokens)                     # (B, S, d)

        aix = state.adapter_ix
        x, new_k, new_v = _layer_loop(
            c, params, x, positions, state.k, state.v,
            blk, off, state.block_tables,
            jnp.where(act0[:, None], positions + 1, 0),  # dead slots: nothing
            bank=bank, adapter_ix=aix, has_lora=jnp.any(act0 & (aix >= 0)),
            attn_impl=attn_impl, partitioned=shardings is not None,
        )
        h = final_norm(c, params, x)
        logits = logits_linear(h, head_weights(params))      # (B, S, V)

        temps = state.temperature
        samp = temps > 0
        greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, S)
        greedy_ok = greedy_tok[:, :k] == drafts                      # (B, k)

        r_u, r_bonus = jax.random.split(rng)
        p_probs = _sampling_probs(logits, temps, state.top_p)        # (B, S, V)
        q_probs = _sampling_probs(qlogits, temps, state.top_p)       # (B, k, V)
        p_at = jnp.take_along_axis(
            p_probs[:, :k], drafts[:, :, None], axis=2
        )[:, :, 0]
        q_at = jnp.take_along_axis(q_probs, drafts[:, :, None], axis=2)[:, :, 0]
        u = jax.random.uniform(r_u, (B, k))
        samp_ok = u * q_at < p_at                # u < p/q without the divide
        ok = jnp.where(samp[:, None], samp_ok, greedy_ok)
        m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)  # (B,)

        # Correction / bonus token at index m: argmax for greedy slots;
        # for sampling slots the residual max(p_m - q_m, 0) normalized
        # (q padded with a zero row at index k, so a fully accepted run
        # falls back to sampling the bonus straight from p_k).
        p_m = jnp.take_along_axis(p_probs, m[:, None, None], axis=1)[:, 0]
        q_pad = jnp.concatenate(
            [q_probs, jnp.zeros_like(q_probs[:, :1])], axis=1
        )
        q_m = jnp.take_along_axis(q_pad, m[:, None, None], axis=1)[:, 0]
        resid = jnp.maximum(p_m - q_m, 0.0)
        r_sum = jnp.sum(resid, axis=-1, keepdims=True)
        resid = jnp.where(r_sum > 0, resid / jnp.maximum(r_sum, 1e-38), p_m)
        bonus_samp = jax.random.categorical(
            r_bonus, jnp.log(jnp.maximum(resid, 1e-38)), axis=-1
        ).astype(jnp.int32)
        bonus_greedy = jnp.take_along_axis(
            greedy_tok, m[:, None], axis=1
        )[:, 0]
        bonus = jnp.where(samp, bonus_samp, bonus_greedy)

        # Emission mirrors the decode step's stop rules: at most `remaining`
        # tokens, and never past cache row ml-2 (the next round's write
        # must still fit).
        cap = jnp.maximum(ml - 1 - lens, 0)
        n_emit = jnp.where(
            act0,
            jnp.minimum(jnp.minimum(m + 1, state.remaining), cap),
            0,
        )
        seq = jnp.concatenate(
            [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1
        )                                            # (B, S): d_1..d_k, _
        seq = jnp.where(offs[None, :] == m[:, None], bonus[:, None], seq)
        emitted = jnp.where(offs[None, :] < n_emit[:, None], seq, -1)

        new_len = lens + n_emit
        new_rem = state.remaining - n_emit
        new_act = act0 & (new_rem > 0) & (new_len + 2 <= ml)
        last_emitted = jnp.take_along_axis(
            emitted, jnp.clip(n_emit - 1, 0, k)[:, None], axis=1
        )[:, 0]
        new_last = jnp.where(n_emit > 0, last_emitted, state.last_token)

        new_state = PagedDecodeState(
            k=new_k,
            v=new_v,
            block_tables=state.block_tables,
            lengths=new_len,
            last_token=new_last,
            active=new_act,
            remaining=new_rem,
            temperature=state.temperature,
            top_p=state.top_p,
            adapter_ix=state.adapter_ix,
        )
        accepted = jnp.where(act0, m, 0)
        return new_state, emitted, accepted, new_act

    if lora:
        @functools.partial(jax.jit, donate_argnums=1, **kw)
        def spec_verify_lora(params, state: PagedDecodeState, drafts,
                             qlogits, rng, lora_bank):
            return _impl(params, state, drafts, qlogits, rng, lora_bank)

        return spec_verify_lora

    @functools.partial(jax.jit, donate_argnums=1, **kw)
    def spec_verify(params, state: PagedDecodeState, drafts, qlogits, rng):
        return _impl(params, state, drafts, qlogits, rng, None)

    return spec_verify


def make_copy_block(shardings=None):
    """copy_block(state, src, dst): copy one pool block across every
    layer — the device half of copy-on-write (the allocator's
    `ensure_writable` picks dst; the engine swaps the table entry)."""
    sh = shardings
    kw = _jit_shardings(
        None if sh is None else (sh.state, sh.replicated, sh.replicated),
        None if sh is None else sh.state,
    )

    @functools.partial(jax.jit, donate_argnums=0, **kw)
    def copy_block(state: PagedDecodeState, src, dst):
        return state._replace(
            k=state.k.at[:, dst].set(state.k[:, src]),
            v=state.v.at[:, dst].set(state.v[:, src]),
        )

    return copy_block
