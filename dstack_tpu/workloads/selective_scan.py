"""The state-space (Mamba-1) recurrence: one plain form and two Pallas
kernels for the paged programs on the TPU.

For each token t of a sequence, with `a` (N, Di) negative and everything
float32:

    h = exp(delta_t * a) * h + B_t (delta_t * u_t)^T        # (N, Di)
    y_t = C_t^T h                                            # (Di,)

A token with delta 0 leaves h as it was (exp(0) = 1, nothing added): how a
chunk's padded tail and a dead row are stopped (`transformer.mamba_mixer`).

- `selective_scan`: the plain form, a `lax.scan` over tokens with
  SCAN_UNROLL of them a loop step. What `forward`, `generate`, the CPU and
  a geometry the kernels do not tile run.
- `selective_scan_decode` (the kernel of a decode step): every slot row is
  one token. It reads and writes the state IN PLACE in the stacked state
  pool at a layer index, as the paged attention kernel reads the KV pool,
  ONE pass over the rows that are live and none over the others: the grid
  walks the slot rows, and a dead row's block index is a live row's, so the
  pipeline fetches and writes back nothing for it. XLA's form of the same
  step read the state in two fusions and wrote it once, for every row:
  16.6 ms a step at 128 slots where the live rows' bytes need 10.4
  (PERF.md section 6, PR 33).
- `selective_scan_chunk` (the kernel of a prefill chunk): one sequence, the
  grid over blocks of channels, each a loop over the chunk's tokens with
  its (N, channels) slice of h in registers: nothing of (tokens, N, Di) is
  written to memory, and a 512-token chunk is no 512 dependent launches.

Both carry their names as the custom call's name: a trace event has no
scope, and `benchmarks/layer_metrics/kernels.ssm_*_roofline.json` find them
by it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens a step of the plain scan's loop takes: the recurrence runs
# SCAN_UNROLL tokens inside one loop body, so a 512-token chunk is 32
# dependent loop steps a layer and not 512, `h` stays in the body between
# them, and nothing of (tokens, d_state, d_inner) is written to memory.
# Never the whole chunk in one body: with no loop left around the
# recurrence XLA copies the whole state pool once a layer to write one
# slot's state back (AOT for the v5e, PR 33: 13 copies of 1.1 GB in the
# 8- and 16-token buckets), so the shortest chunks take two steps.
SCAN_UNROLL = 16
# Channels a grid step of the chunk kernel keeps h for: (16, 512) float32
# is 8 vector registers.
CHUNK_CHANNELS = 512
# Tokens the chunk kernel reads B and C for at once (one lane each).
TOKEN_GROUP = 16


def scan_impl(d_state: int, d_inner: int, interpret: bool = False) -> str:
    """"pallas" where the kernels tile the state (whole sublanes of
    d_state, whole lanes of d_inner) and the backend is a TPU, else "lax"."""
    tiles = d_state % 8 == 0 and d_inner % 128 == 0
    on_tpu = interpret or jax.default_backend() == "tpu"
    return "pallas" if tiles and on_tpu else "lax"


def selective_scan(delta, u, b_in, c_out, a, h0):
    """delta, u (B, S, Di); b_in, c_out (B, S, N); a (N, Di); h0 (B, N, Di)
    -> (y (B, S, Di), h after the last token), float32."""
    def step(h, xs):
        d, du, bt, ct = xs                     # (B, Di), (B, Di), (B, N), (B, N)
        h = jnp.exp(d[:, None, :] * a) * h + bt[:, :, None] * du[:, None, :]
        return h, jnp.sum(h * ct[:, :, None], axis=1)

    if delta.shape[1] == 1:                    # a decode step: no loop
        h, y = step(h0, (delta[:, 0], (delta * u)[:, 0], b_in[:, 0], c_out[:, 0]))
        return y[:, None], h
    by_token = lambda x: jnp.moveaxis(x, 1, 0)
    h, y = lax.scan(
        step, h0, tuple(map(by_token, (delta, delta * u, b_in, c_out))),
        unroll=max(1, min(SCAN_UNROLL, delta.shape[1] // 2)),
    )
    return by_token(y), h


# ---------------------------------------------------------------- decode kernel


def _decode_kernel(layer_ref, block_ref, live_ref, d_ref, du_ref, b_ref, c_ref,
                   a_ref, h_ref, y_ref, h_out_ref):
    row = pl.program_id(0)
    live = live_ref[row] > 0

    @pl.when(live)
    def _():
        h = jnp.exp(d_ref[...] * a_ref[...]) * h_ref[...] + b_ref[...] * du_ref[...]
        h_out_ref[...] = h
        y_ref[...] = jnp.sum(h * c_ref[...], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # No row is live and this is the block every row was pointed at: it is
    # written back all the same, so hand it through.
    @pl.when(jnp.logical_not(live) & (block_ref[row] == row))
    def _():
        h_out_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_decode(pool, layer, live, delta, u, b_in, c_out, a, *,
                          interpret=False):
    """One decode step of one state-space layer over every slot row, the
    state updated in place. pool (Ls, B, N, Di) float32; layer () int32;
    live (B,) bool; delta, u (B, Di); b_in, c_out (B, N); a (N, Di), float32
    -> (y (B, Di), the pool). Rows that are not live keep their state bit
    for bit (they are not read either) and give y 0."""
    ls, b, n, di = pool.shape
    rows = jnp.arange(b, dtype=jnp.int32)
    # A dead row's block is the last live row's before it (the first live
    # row's for those ahead of it; row 0's where none is live): an index the
    # pipeline already holds, so it moves nothing.
    last = lax.cummax(jnp.where(live, rows, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    block = jnp.where(last >= 0, last, first)
    by_row = lambda r, lyr, blk, lv: (r, 0, 0)
    state = lambda r, lyr, blk, lv: (lyr[0], blk[r], 0, 0)
    y, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, 1, di), by_row),           # delta
                pl.BlockSpec((None, 1, di), by_row),           # delta * u
                pl.BlockSpec((None, n, 1), by_row),            # B
                pl.BlockSpec((None, n, 1), by_row),            # C
                pl.BlockSpec((n, di), lambda r, lyr, blk, lv: (0, 0)),
                pl.BlockSpec((None, None, n, di), state),
            ],
            out_specs=[
                pl.BlockSpec((None, 1, di), by_row),
                pl.BlockSpec((None, None, n, di), state),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, di), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="selective_scan_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), block, live.astype(jnp.int32),
        delta[:, None], (delta * u)[:, None], b_in[:, :, None], c_out[:, :, None],
        a, pool,
    )
    return y[:, 0], pool


# ----------------------------------------------------------------- chunk kernel


def _chunk_kernel(d_ref, du_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_ref, *,
                  group):
    a = a_ref[...]
    tokens = d_ref.shape[0]

    def some_tokens(g, h):
        at = pl.multiple_of(g * group, group)
        d, du = d_ref[pl.ds(at, group), :], du_ref[pl.ds(at, group), :]
        bg, cg = b_ref[g], c_ref[g]                      # (N, group): a token a lane
        for j in range(group):
            h = jnp.exp(d[j:j + 1] * a) * h + bg[:, j:j + 1] * du[j:j + 1]
            y_ref[pl.ds(at + j, 1), :] = jnp.sum(h * cg[:, j:j + 1], axis=0, keepdims=True)
        return h

    h_ref[...] = lax.fori_loop(0, tokens // group, some_tokens, h0_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_chunk(delta, u, b_in, c_out, a, h0, *, interpret=False):
    """One sequence's chunk through one state-space layer. delta, u (S, Di);
    b_in, c_out (S, N); a, h0 (N, Di), float32 -> (y (S, Di), h after the
    last token). S is a multiple of 8."""
    s, di = delta.shape
    n = a.shape[0]
    group = min(TOKEN_GROUP, s)
    width = CHUNK_CHANNELS if di % CHUNK_CHANNELS == 0 else 128
    # B and C by groups of tokens, a token a LANE: the kernel needs B_t as
    # a column (a state value a sublane) to spread over the channels.
    grouped = lambda x: jnp.swapaxes(x.reshape(s // group, group, n), 1, 2)
    channels = lambda i: (0, i)
    whole = lambda i: (0, 0, 0)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, group=group),
        grid=(di // width,),
        in_specs=[
            pl.BlockSpec((s, width), channels),
            pl.BlockSpec((s, width), channels),
            pl.BlockSpec((s // group, n, group), whole),
            pl.BlockSpec((s // group, n, group), whole),
            pl.BlockSpec((n, width), channels),
            pl.BlockSpec((n, width), channels),
        ],
        out_specs=[
            pl.BlockSpec((s, width), channels),
            pl.BlockSpec((n, width), channels),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, di), jnp.float32),
            jax.ShapeDtypeStruct((n, di), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="selective_scan_chunk",
    )(delta, delta * u, grouped(b_in), grouped(c_out), a, h0)
