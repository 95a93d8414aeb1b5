"""Multi-tenant LoRA serving: batched adapter multiplexing over one engine.

`workloads/lora.py` trains adapters and `merge_lora` bakes one adapter
into a dedicated replica — one tenant per engine. This module is the
serving half of multi-tenancy: a host-side refcounted adapter registry
backed by a device-side adapter pool, so ONE batched decode step serves
mixed tenants.

Layout: the pool holds `max_adapters + 1` slots per target projection,
`(L, P, d_in, r)` for A and `(L, P, r, d_out)` for B, with the extra
last slot permanently zero — the landing pad for `adapter_id == -1`
(no-adapter) requests. Inside the jitted decode/prefill/verify programs
each batch slot gathers its own A/B pair by index (the `workloads/moe.py`
gather/dispatch pattern) and applies `y += (alpha/r)·(x@A)@B` UNMERGED on
the LoRA target projections. The delta is added to the projection output
before reshape/RoPE — the same place `merge_lora`'s baked-in delta lands —
so a multiplexed engine computes a merged single-tenant engine's logits
to bf16 rounding (the delta is added in f32 here and rounded into the
bf16 weights there, so a top-2 near-tie may resolve differently;
tests/test_lora_serving.py writes the tolerance). When no live slot carries an adapter, a `lax.cond` skips the
gather+einsum entirely, so adapter-free batches pay one predicate, not
two matmuls per target — and when no in-flight request holds an adapter
ref at all (`AdapterRegistry.inflight == 0`), the engine dispatches its
plain program twins host-side, so the idle-pool path is byte-identical
to a LoRA-free engine.

Host side: `AdapterRegistry` maps adapter names to pool slots with
refcounts (every in-flight request holds a ref) and LRU eviction of idle
adapters under slot pressure; evicting or unloading an adapter with
in-flight requests is refused. The registry is NOT thread-safe on its
own — `ServingEngine` calls it under its scheduler lock.
"""

from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dstack_tpu.workloads.config import FULL, ModelConfig
from dstack_tpu.workloads.lora import DEFAULT_TARGETS
from dstack_tpu.workloads.transformer import _rope, linear, rms_norm

Params = Dict[str, Any]

# Attention projections the multiplexed path supports: the delta rides
# inside `project_qkv_lora`, which only recomputes the q/k/v projections.
SUPPORTED_TARGETS = ("wq", "wk", "wv")


class AdapterPoolFullError(RuntimeError):
    """Every pool slot is held by an adapter with in-flight requests."""


class AdapterBusyError(RuntimeError):
    """Unload/replace refused: the adapter has in-flight requests."""


def make_lora_bank(
    config: ModelConfig,
    base: Params,
    *,
    max_adapters: int,
    rank: int,
    targets: Sequence[str] = DEFAULT_TARGETS,
) -> Params:
    """Zero-initialised device pool. Slot `max_adapters` (the +1) stays
    all-zero forever: gathers for adapter_id=-1 land there and contribute
    an exactly-zero delta."""
    if max_adapters < 1:
        raise ValueError(f"max_adapters must be >= 1, got {max_adapters}")
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")
    bad = [t for t in targets if t not in SUPPORTED_TARGETS]
    if bad:
        raise ValueError(
            f"unsupported LoRA serving targets {bad}; multiplexed serving"
            f" covers the attention projections {SUPPORTED_TARGETS}"
        )
    pool = max_adapters + 1
    layers: Params = {}
    for t in targets:
        w = base["layers"][t]
        if not hasattr(w, "shape"):
            raise ValueError(
                f"target {t!r} is not a plain weight (quantized base?)"
            )
        n_layers, d_in, d_out = w.shape
        layers[f"{t}_a"] = jnp.zeros((n_layers, pool, d_in, rank), w.dtype)
        layers[f"{t}_b"] = jnp.zeros((n_layers, pool, rank, d_out), w.dtype)
    return {"scale": jnp.zeros((pool,), jnp.float32), "layers": layers}


def project_qkv_lora(c, x, p, positions, lp, adapter_ix, scale, has_lora):
    """`transformer.project_qkv` plus per-slot unmerged LoRA deltas.

    `lp` is one layer's slice of the pool (`(P, d_in, r)` / `(P, r, d_out)`
    per target), `adapter_ix` the already-sanitised pool index — scalar for
    the single-request prefill program, `(B,)` for batched decode/verify —
    and `scale` the matching per-request `alpha/r`. `has_lora` gates the
    whole LoRA-aware projection behind ONE `lax.cond` per layer: the dead
    branch is byte-for-byte the plain q/k/v projection (no f32 casts, no
    zero adds), so adapter-free steps pay one predicate, not the feature.
    """
    with jax.named_scope("attn/qkv"):
        b, s, _ = x.shape
        hd = c.head_dim
        h = rms_norm(x, p["attn_norm"], c.norm_eps)

        def _plain(_):
            return (linear(h, p["wq"]), linear(h, p["wk"]), linear(h, p["wv"]))

        def _with_lora(_):
            hf = h.astype(jnp.float32)

            def _delta(name: str):
                a_pool, b_pool = lp[f"{name}_a"], lp[f"{name}_b"]
                if adapter_ix.ndim == 0:  # chunked prefill: one request
                    a = a_pool[adapter_ix].astype(jnp.float32)
                    bm = b_pool[adapter_ix].astype(jnp.float32)
                    t = jnp.einsum("bsd,dr->bsr", hf, a)
                    return jnp.einsum("bsr,ro->bso", t, bm) * scale
                a = jnp.take(a_pool, adapter_ix, axis=0).astype(jnp.float32)
                bm = jnp.take(b_pool, adapter_ix, axis=0).astype(jnp.float32)
                t = jnp.einsum("bsd,bdr->bsr", hf, a)
                return jnp.einsum("bsr,bro->bso", t, bm) * scale[:, None, None]

            def proj(name: str):
                y = linear(h, p[name])
                if f"{name}_a" in lp:
                    y = (y.astype(jnp.float32) + _delta(name)).astype(y.dtype)
                return y

            return (proj("wq"), proj("wk"), proj("wv"))

        q, k, v = lax.cond(has_lora, _with_lora, _plain, 0)
        q = q.reshape(b, s, c.n_heads, hd)
        k = k.reshape(b, s, c.n_kv_heads, hd)
        v = v.reshape(b, s, c.n_kv_heads, hd)
        rope = c.rope(FULL)
        return _rope(q, positions, rope), _rope(k, positions, rope), v


class AdapterRegistry:
    """Name -> pool-slot map with refcounts and LRU slot eviction.

    Thread-unsafe by design: `ServingEngine` already serialises scheduler
    state behind one lock, and the registry lives inside it.
    """

    def __init__(
        self,
        config: ModelConfig,
        base: Params,
        *,
        max_adapters: int,
        rank: int,
        targets: Sequence[str] = DEFAULT_TARGETS,
        mesh=None,
    ):
        self.config = config
        self.max_adapters = max_adapters
        self.rank = rank
        self.targets = tuple(targets)
        self._mesh = mesh
        self.bank = self._put(
            make_lora_bank(
                config, base, max_adapters=max_adapters, rank=rank,
                targets=targets,
            )
        )
        self._slots: Dict[str, int] = {}
        self._refs: Dict[str, int] = {}
        self._alphas: Dict[str, float] = {}
        self._lru: "OrderedDict[str, None]" = OrderedDict()
        self._free = list(range(max_adapters))

    def _put(self, tree):
        if self._mesh is None:
            return tree
        # Adapters are tiny relative to base weights: replicate them so
        # the in-program contractions stay replicated and tensor-parallel
        # serving keeps its bit-exactness guarantee.
        spec = NamedSharding(self._mesh, P())
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, spec), tree)

    # ------------------------------------------------------------- queries

    @property
    def loaded_count(self) -> int:
        return len(self._slots)

    @property
    def inflight(self) -> int:
        """Requests currently holding an adapter ref. Zero means no live
        batch slot can carry an adapter, so the engine may dispatch the
        plain (LoRA-free) jitted programs for the step — the lax.cond
        inside the LoRA programs skips the adapter math but still costs
        fusion breaks the base path shouldn't pay."""
        return sum(self._refs.values())

    def loaded(self) -> Dict[str, Dict[str, Any]]:
        return {
            name: {
                "slot": ix,
                "refs": self._refs.get(name, 0),
                "alpha": self._alphas.get(name, 0.0),
                "rank": self.rank,
            }
            for name, ix in self._slots.items()
        }

    def slot_of(self, name: str) -> Optional[int]:
        return self._slots.get(name)

    # ----------------------------------------------------------- lifecycle

    def load(self, name: str, adapter: Params, *, alpha: float = 16.0) -> int:
        """Install (or replace) an adapter; returns its pool slot.

        Replacing weights under in-flight requests would change tokens
        mid-stream, so a busy adapter refuses the reload."""
        layers = adapter.get("layers") if isinstance(adapter, dict) else None
        if not layers:
            raise ValueError("adapter must be a {'layers': {...}} pytree")
        expect = {f"{t}_{ab}" for t in self.targets for ab in ("a", "b")}
        if set(layers) != expect:
            raise ValueError(
                f"adapter targets {sorted(layers)} != engine targets"
                f" {sorted(expect)}"
            )
        for t in self.targets:
            a, b = layers[f"{t}_a"], layers[f"{t}_b"]
            pool_a = self.bank["layers"][f"{t}_a"]
            want_a = (pool_a.shape[0],) + pool_a.shape[2:]
            if tuple(a.shape) != want_a:
                raise ValueError(
                    f"{t}_a shape {tuple(a.shape)} != {want_a}"
                    f" (engine rank is {self.rank})"
                )
            if tuple(b.shape)[:2] != (pool_a.shape[0], self.rank):
                raise ValueError(
                    f"{t}_b shape {tuple(b.shape)} incompatible with"
                    f" rank {self.rank}"
                )
        if name in self._slots:
            if self._refs.get(name, 0) > 0:
                raise AdapterBusyError(
                    f"adapter {name!r} has {self._refs[name]} in-flight"
                    " request(s); reload refused"
                )
            ix = self._slots[name]
        else:
            ix = self._free.pop() if self._free else self._evict_one()
            self._slots[name] = ix
            self._refs[name] = 0
        new_layers = dict(self.bank["layers"])
        for key in expect:
            leaf = new_layers[key]
            new_layers[key] = leaf.at[:, ix].set(
                jnp.asarray(layers[key], leaf.dtype)
            )
        scale = self.bank["scale"].at[ix].set(float(alpha) / self.rank)
        self.bank = self._put({"scale": scale, "layers": new_layers})
        self._alphas[name] = float(alpha)
        self._lru[name] = None
        self._lru.move_to_end(name)
        return ix

    def _evict_one(self) -> int:
        for name in self._lru:  # least-recently-used first
            if self._refs.get(name, 0) == 0:
                ix = self._slots.pop(name)
                del self._lru[name]
                self._refs.pop(name, None)
                self._alphas.pop(name, None)
                return ix
        raise AdapterPoolFullError(
            f"all {self.max_adapters} adapter slots have in-flight requests"
        )

    def unload(self, name: str) -> None:
        if name not in self._slots:
            raise KeyError(f"adapter {name!r} is not loaded")
        if self._refs.get(name, 0) > 0:
            raise AdapterBusyError(
                f"adapter {name!r} has {self._refs[name]} in-flight"
                " request(s); unload refused"
            )
        ix = self._slots.pop(name)
        self._refs.pop(name, None)
        self._alphas.pop(name, None)
        self._lru.pop(name, None)
        # Zero the vacated slot: a stale gather against a freed index must
        # read zeros, not the unloaded tenant's weights.
        new_layers = {
            key: leaf.at[:, ix].set(0)
            for key, leaf in self.bank["layers"].items()
        }
        scale = self.bank["scale"].at[ix].set(0.0)
        self.bank = self._put({"scale": scale, "layers": new_layers})
        self._free.append(ix)

    # ------------------------------------------------------------ refcounts

    def acquire(self, name: str) -> int:
        """Take an in-flight ref; returns the pool slot for the request."""
        if name not in self._slots:
            raise KeyError(f"adapter {name!r} is not loaded")
        self._refs[name] = self._refs.get(name, 0) + 1
        self._lru.move_to_end(name)
        return self._slots[name]

    def release(self, name: str) -> None:
        n = self._refs.get(name, 0)
        if n > 0:
            self._refs[name] = n - 1


# ------------------------------------------------------------------- I/O

def save_adapter(path: str, adapter: Params, *, rank: int,
                 alpha: float = 16.0) -> None:
    """Adapter-only export (the serving-side peer of checkpoint exports).

    Leaves are widened to float32 on disk: npz round-trips bfloat16 as
    raw void bytes, and f32 represents every bf16/f16 value exactly —
    the registry casts back to the pool dtype at load."""
    import numpy as np

    flat = {
        f"layers.{k}": np.asarray(jnp.asarray(v, jnp.float32))
        for k, v in adapter["layers"].items()
    }
    np.savez(path, __rank__=rank, __alpha__=alpha, **flat)


def load_adapter_file(path: str) -> Tuple[Params, int, float]:
    import numpy as np

    z = np.load(path)
    layers = {
        k.split(".", 1)[1]: jnp.asarray(z[k])
        for k in z.files
        if k.startswith("layers.")
    }
    if not layers:
        raise ValueError(f"{path} holds no adapter layers")
    return (
        {"layers": layers},
        int(z["__rank__"]),
        float(z["__alpha__"]),
    )


def demo_adapter(
    config: ModelConfig,
    base: Params,
    key: jax.Array,
    *,
    rank: int,
    targets: Sequence[str] = DEFAULT_TARGETS,
    scale: float = 0.05,
) -> Params:
    """Random NON-zero adapter (unlike `lora_init`, B != 0) so demo/bench
    tenants produce visibly different generations without a training run."""
    layers: Params = {}
    for i, t in enumerate(targets):
        w = base["layers"][t]
        n_layers, d_in, d_out = w.shape
        ka = jax.random.fold_in(key, 2 * i)
        kb = jax.random.fold_in(key, 2 * i + 1)
        layers[f"{t}_a"] = (
            jax.random.normal(ka, (n_layers, d_in, rank), jnp.float32)
            * d_in**-0.5
        ).astype(w.dtype)
        layers[f"{t}_b"] = (
            jax.random.normal(kb, (n_layers, rank, d_out), jnp.float32) * scale
        ).astype(w.dtype)
    return {"layers": layers}
