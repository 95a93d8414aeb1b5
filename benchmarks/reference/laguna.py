"""Plain reference of the `laguna` decoder (poolside Laguna-S-2.1): float32
`jax.numpy`, `default_matmul_precision("highest")`, no cache, no kernels, no
periods, no expert capacity, no sorting. It decides `correct` and the program
cannot change it.

Follows the published configuration (poolside/Laguna-S-2.1 `config.json`,
`model_type` laguna) and, for the scaled rotary embedding, the YaRN paper
(arXiv:2309.00071) as `transformers` computes it for a partially rotated head
(`_compute_yarn_parameters` with `dim = head_dim x partial_rotary_factor`).
Layer `l` has the kind `layer_types[l]` and H = `num_attention_heads_per_layer
[l]` query heads (48 on a full layer, 72 on a sliding layer, 8 KV heads of 128
on both); `x` is the block input:

1. `h = RMSNorm(x)`; `q = h Wq -> (S, H, hd)`, `k = h Wk`, `v = h Wv -> (S,
   KV, hd)`, no bias. ASSUMED: no query/key norm (the file has no key for
   one; the alternative, an RMSNorm a head on q and k, would add two leaves a
   layer that the published count of 117.56 B leaves no room for).
2. RoPE BY THE LAYER'S KIND, rotate-half (ASSUMED pairing: column i with
   column i + rot/2 inside the rotated slice, the form `transformers` uses;
   seeded weights cannot tell a pairing apart). A `sliding_attention` layer
   rotates all `hd` values, `inv_freq_i = theta^(-2i / hd)`, theta 10,000. A
   `full_attention` layer rotates the FIRST `rot = hd x partial_rotary_factor`
   = 64 values and passes the other 64 unrotated and unscaled; its
   frequencies are YaRN's over the rotated width: `extra_i = theta^(-2i /
   rot)`, `inter = extra / factor`, `low, high = floor, ceil` (ASSUMED
   `truncate` true, the library's default) of `rot ln(orig / (beta 2 pi)) /
   (2 ln theta)` for `beta = beta_fast, beta_slow`, `ramp_i = clip((i - low) /
   (high - low), 0, 1)`, `inv_freq' = inter ramp + extra (1 - ramp)`; cos and
   sin of the rotated slice are multiplied by `attention_factor` (1.4852 =
   0.1 ln 128 + 1). Computed here in float64 from the published group,
   independently of `config.RopeParams`.
3. Scores `q k^T / sqrt(hd)`, H / KV query heads a KV head (query head a
   reads KV head floor(a / (H / KV))); key `j` is visible to query `i` iff
   `j <= i` and, in a sliding layer, `i - j < sliding_window`. Softmax, `o_a
   = P_a v`. Scores are taken a block of query rows at a time.
4. THE GATE (`gating` "per-head"): `g = act(h Wg) -> (S, H)`, `Wg` hidden x
   H, from the same normed input as the queries, float32; head a's output is
   `g_a o_a`; `x += concat_a(g_a o_a) Wo`. ASSUMED: `act` = softplus. The
   alternative is the sigmoid head-wise gate of arXiv:2505.06708; the program
   has both (`ModelConfig.attn_gate`), this file runs what the configuration
   it is handed names (`GATES`), and the cell's `model.json` lists softplus
   under `assumed`. Either is one elementwise function on a tokens x H tensor.
5. The MLP. Layer 0 (`mlp_only_layers` [0]): SwiGLU of width
   `intermediate_size`. Every other layer: `h2 = RMSNorm(x)`; `s = sigmoid(h2
   Wr)` over ALL `num_experts` published (router float32; ASSUMED: sigmoid
   scores beside a selection bias `b` of zero that chooses and does not
   weigh, the published form of a router with `moe_routed_scaling_factor`
   and `norm_topk_prob`; the alternative is a softmax over the experts;
   `moe_router_logit_softcapping` 0 = off); the k largest of `s + b`; `w = s /
   sum_chosen s x scaling`; `y = sum_e w_e SwiGLU_e(h2) + SwiGLU_shared(h2)`
   (weights on the output: `moe_apply_router_weight_on_input` false); `x +=
   y`.
6. THE SHARE. The reference is given the share the program is given: the
   bank's leaves hold experts `experts_first .. experts_first + held - 1`
   of the `n_experts` the router scores. The sum of 5 runs over the experts
   HELD: a chosen expert that is held elsewhere adds nothing, here as in the
   program, and that partial sum plus the shared expert goes on to the next
   layer. With the whole bank held it is the whole layer
   (`tests/test_laguna_model.py` adds the shares up to it). A sliced
   vocabulary is a smaller vocabulary: ids and logits over the rows held.
7. Final RMSNorm, untied head.

Departures, each for a reason:

- Weights come from the program's `transformer.init_params` tree (`x @ w`
  layout; `dense_layers` then `layers`, and `wq`, `wo`, `wg` in
  `dense_mixers` / `mixers` by kind where the kinds differ in their heads)
  because the comparison is on the same seeded weights; only the layout is
  taken. A layer's KIND, its head count and whether it is dense are read here
  from the configuration's lists, one layer at a time.
- No load-balance term: a sigmoid router with a selection bias has none;
  `loss` returns aux 0 (no train cell uses it).
- One layer runs at a time with its weights cast to float32 on the way in,
  one expert at a time inside it, ROW_GROUP sequences at a time, so the
  reference fits beside 11.14 GB of bf16 weights on one chip.

Tolerances. With `init_params` weights the logits at a position are close to
standard normal, so tolerances are in units of the reference logits' standard
deviation (sd). The served model is bf16 (weights and activations, float32
accumulation, float32 router and gate); the reference is float32 on the same
weights.

ROUTING FLIPS at 256 experts top-10 are at nearly every position (the 10th
and 11th of 256 sigmoid scores are within 0.002 of each other in some layer
at most positions) and SMALL: a flipped expert is the 10th of ten, and on a
share only a flip that lands on, or leaves, an expert held here moves
anything. ROUTER_TIE_MARGIN = 0.0005 leaves out only the positions where a
layer's 10th and 11th `s + b` are closer than what a bf16 input moves a
score by; the rules below are sized with the other flips in.

LOGIT_MEDIAN_TOL = 0.15 sd and LOGIT_RMS_TOL = 0.25 sd, where logits can be
read (the CPU tests: `forward`, and chunked prefill then decode through the
paged pool): over the positions kept, the MEDIAN of the per-position RMS logit
error and the RMS over all of them. Both must hold. Readings (CPU, PR 37; the
`tiny-laguna` preset widened to the cell's 256 experts top-10 with 128 held
and no token dropped, hidden 256, experts of 64, vocabulary 8,192, window 16;
8 rows x 128 positions, seeds 1-4; ~30% of the positions are near-ties and
left out), median / RMS: the bf16 program 0.061-0.072 / 0.121-0.140; weights
rounded to e4m3 0.371-0.380 / 0.391-0.407; the gate's other activation
0.842-0.874 / 0.860-0.881; a full layer's head count on the sliding layers
0.928-0.967 / 0.941-0.976; no gate 0.985-1.012 / 0.988-1.015; the whole head
rotated on the full layers 1.152-1.162 / 1.121-1.129. 0.15 is 2.1 times the
worst bf16 median and 0.40 of the best e4m3 one; 0.25 is 1.8 times the worst
bf16 RMS and 0.64 of e4m3's. ON THE CHIP at the cut's widths the reference on
e4m3-rounded weights reads, at the probe's first new position (9-13 positions
kept of 16-20, three seeds), median / RMS 0.446 / 0.446, 0.403 / 0.408, 0.449
/ 0.434; without the gate 1.084 / 1.070, 1.127 / 1.103; with the sigmoid gate
0.812 / 0.802, 0.889 / 0.875 (my chip runs, PR 37). At `tiny-laguna` itself (8 experts top-3 with scaling 2.5: one
flip is most of a layer) the bf16 program reads 0.028-0.030 / 0.142-0.158.

LOGIT_TOL = 0.1 sd, PASS_SHARE = 0.6, MIN_CHECKED = 8, where only tokens can
be read (the engine's probe): the SHARE RULE of `reference/mellum.py` with
this model's own numbers. A greedy token the engine returns is within
tolerance when its reference logit is within LOGIT_TOL sd (of that position's
logits) of the reference's maximum; at least PASS_SHARE of the checked
positions must be, and at least MIN_CHECKED must have been checked. A token is
checked for as long as the engine's earlier tokens follow the reference's own
greedy path, so a row has at most ONE position outside (its last), and what
the rule counts is rows that END outside against positions checked: the probe
is 20 rows x 2,048 tokens x 8 new tokens (`cuts/serve.json`; 20 is what a
rehearsal's server of 4 slots and 16 pending places takes). With routing
flips at nearly every position a bf16 row leaves the reference's path often
and mostly onto a token far below the reference's best (a flipped expert is
a tenth of 2.5 times the routed sum): its gaps are under 0.02 sd or over
0.1, little between. Readings ON THE CHIP at the cell's widths (my chip
runs, PR 37; share of checked positions WITHIN 0.1 sd | rows of 20 that end
outside): the bf16 engine, six seeds of the final probe: 0.857, 0.893,
0.814, 0.860, 0.886, 0.890 | 12, 9, 11, 12, 8, 10 (59-91 positions checked);
two seeds of a 16 x 6 probe 0.900, 0.937; the plain reference fed
e4m3-rounded weights (rounded in float32 arithmetic: `astype` under `jit`
moves nothing on the v5e), two seeds of the final probe: 0.345, 0.320 | 19, 17
(29, 25 checked) and one of the 16 x 6 probe 0.545; without the gate 0.0 | 20
of 20 at the first token; with the sigmoid gate 0.0 | 20 of 20. 0.6 lies 0.21
under the worst bf16 reading and 0.26 over the best e4m3 one of the final
probe. A bf16 engine's positions end outside at a rate of about 0.125 (its
~80 checked positions would have to at 0.4: seven of its standard deviations
off); an e4m3 copy's at 0.65-0.68 over ~27 positions (0.4 is three of its
standard deviations off, about 1 run in 500). On the CPU at the widening above
(32 rows x 8 tokens, seeds 1-3): bf16 0.901-0.911 within, e4m3 0.395-0.463, no
gate and the sigmoid gate 0.0-0.09 (`benchmarks/tests/test_reference_laguna.py`).
`check_tokens` also reports how many checked positions lie outside each
tolerance of GAP_LADDER, so the next calibration needs no new run.
"""

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# The readings behind each are in the module's note.
LOGIT_MEDIAN_TOL = 0.15
LOGIT_RMS_TOL = 0.25
LOGIT_TOL = 0.1
PASS_SHARE = 0.6
ROUTER_TIE_MARGIN = 0.0005
MIN_CHECKED = 8
LOSS_TOL = 0.02
AUX_TOL = 0.02
GAP_LADDER = (0.02, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3)
ROW_BLOCK = 256  # query rows whose scores are live at once
ROW_GROUP = 8    # sequences that go through the layers together

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
GATES = {"softplus": jax.nn.softplus, "sigmoid": jax.nn.sigmoid}


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope_frequencies(group: Dict[str, Any], head_dim: int):
    """(rotated width, its inverse frequencies (width / 2,) as floats, the
    factor on cos and sin) of one published `rope_parameters` group, in
    float64."""
    dim = int(head_dim * float(group.get("partial_rotary_factor", 1.0))) // 2 * 2
    theta = float(group["rope_theta"])
    extra = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if group.get("rope_type", "default") == "default":
        return dim, extra, 1.0
    assert group["rope_type"] == "yarn", group
    factor = float(group["factor"])
    orig = float(group["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = correction(float(group.get("beta_fast", 32)))
    high = correction(float(group.get("beta_slow", 1)))
    if group.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    scale = group.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return dim, out, float(scale)


def _rope(x, rot, inv_freq, scale):
    """x: (B, S, H, d), positions 0..S-1: the first `rot` values of a head
    rotated, rotate-half pairing inside them; the rest as they are."""
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1)


def _attention(c: Dict[str, Any], kind: str, h: int, x, p):
    b, s, _ = x.shape
    kv, hd = c["n_kv_heads"], c["head_dim"]
    rot, inv_freq, scale = rope_frequencies(dict(dict(c["rope"])[kind]), hd)
    xn = _rms_norm(x, p["attn_norm"], c["norm_eps"])
    q = _rope((xn @ p["wq"]).reshape(b, s, h, hd), rot, inv_freq, scale)
    k = _rope((xn @ p["wk"]).reshape(b, s, kv, hd), rot, inv_freq, scale)
    v = (xn @ p["wv"]).reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)
    key_pos = jnp.arange(s)
    rows = []
    # A block of query rows at a time: the (H, S, S) float32 scores of a
    # long prompt would not fit beside the weights.
    for r0 in range(0, s, ROW_BLOCK):
        qb = q[:, r0:r0 + ROW_BLOCK]
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * hd ** -0.5
        gap = (r0 + jnp.arange(qb.shape[1]))[:, None] - key_pos[None, :]
        seen = gap >= 0
        if kind == SLIDING:
            seen &= gap < c["sliding_window"]
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        rows.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, v))
    out = jnp.concatenate(rows, axis=1).reshape(b, s, h, hd)
    if c["attn_gate"]:
        out = out * GATES[c["attn_gate"]](xn @ p["wg"])[..., None]
    return x + out.reshape(b, s, h * hd) @ p["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _mlp(c: Dict[str, Any], x, p):
    """-> (x, router margin (B, S): the k-th largest `s + b` minus the
    (k+1)-th, +inf for a dense layer)."""
    xn = _rms_norm(x, p["mlp_norm"], c["norm_eps"])
    if "router" not in p:
        out = _swiglu(xn, p["w_gate"].astype(F32), p["w_up"].astype(F32),
                      p["w_down"].astype(F32))
        return x + out, jnp.full(x.shape[:2], jnp.inf, F32)
    n_experts, k = c["n_experts"], c["experts_per_token"]
    logits = xn @ p["router"]                                     # (B, S, E)
    scores = (jax.nn.sigmoid(logits) if c["router_score"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choose = scores + p["router_bias"] if "router_bias" in p else scores
    ranked, top_i = jax.lax.top_k(choose, min(k + 1, n_experts))
    margin = (ranked[..., k - 1] - ranked[..., k]
              if n_experts > k else jnp.full(x.shape[:2], jnp.inf, F32))
    chosen = jax.nn.one_hot(top_i[..., :k], n_experts, dtype=F32)  # (B, S, k, E)
    weight = jnp.sum(chosen, axis=2) * scores                     # (B, S, E)
    weight = c["routed_scaling"] * weight / jnp.sum(weight, axis=-1, keepdims=True)
    # The experts HELD: leaf j of the bank is expert `experts_first + j`.
    first, held = c["experts_first"], p["we_gate"].shape[0]
    weight = weight[..., first:first + held]

    def one_expert(out, e):
        gate, up, down, w = e
        y = _swiglu(xn, gate.astype(F32), up.astype(F32), down.astype(F32))
        return out + w[..., None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p["we_gate"], p["we_up"], p["we_down"], jnp.moveaxis(weight, -1, 0)))
    if "ws_gate" in p:
        out = out + _swiglu(xn, p["ws_gate"].astype(F32), p["ws_up"].astype(F32),
                            p["ws_down"].astype(F32))
    return x + out, margin


SMALL = ("wq", "wk", "wv", "wo", "wg", "attn_norm", "mlp_norm", "router",
         "router_bias")


def _layer(c_items, kind, heads, x, stack, index, own, rank):
    """One `kind` block of `heads` query heads on `x`, with layer `index` of
    a stack of weights and, where the kinds have stacks of their own, layer
    `rank` of this kind's (`own`). The slices are taken inside the jitted
    function, next to the cast to float32, so no second copy of a layer's
    bf16 weights is ever held."""
    c = dict(c_items)
    take = lambda tree, i: jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False), tree)
    p = take(stack, index)
    if own is not None:
        p = {**p, **take(own, rank)}
    small = {k: p[k].astype(F32) for k in SMALL if k in p}
    return _mlp(c, _attention(c, kind, heads, x, small), {**p, **small})


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 2))


def _group(group) -> tuple:
    """One kind's rotary group as sorted items, from the published mapping
    or from the program's normalised form (`theta` for `rope_theta`)."""
    group = dict(group if isinstance(group, dict) else vars(group))
    if "theta" in group:
        group["rope_theta"] = group.pop("theta")
    return tuple(sorted(group.items()))


def _sizes(config) -> Dict[str, Any]:
    """The sizes the reference reads, from a ModelConfig or from the fields
    a cell resolves (`cellfiles.resolve_model`): hashable, for the jit."""
    f = config if isinstance(config, dict) else vars(config)
    n = f["n_layers"]
    c = {k: f[k] for k in ("d_model", "n_kv_heads", "n_layers", "norm_eps",
                           "n_experts", "experts_per_token")}
    c["head_dim"] = f.get("head_size") or f["d_model"] // f["n_heads"]
    c["sliding_window"] = int(f.get("sliding_window") or 0)
    c["attn_gate"] = f.get("attn_gate") or ""
    c["n_dense_layers"] = int(f.get("n_dense_layers") or 0)
    c["routed_scaling"] = float(f.get("routed_scaling", 1.0))
    c["router_score"] = f.get("router_score", "softmax")
    c["experts_first"] = int(f.get("experts_first") or 0)
    c["layer_types"] = tuple(f.get("layer_types") or ())[:n] or (FULL,) * n
    c["heads"] = tuple(f.get("heads_per_layer") or ())[:n] or (f["n_heads"],) * n
    ropes = dict(f.get("rope_parameters") or ())
    plain = {"rope_type": "default", "rope_theta": f.get("rope_theta", 500000.0)}
    c["rope"] = tuple((kind, _group(ropes.get(kind, plain)))
                      for kind in (FULL, SLIDING))
    return c


def _layers(c: Dict[str, Any], params):
    """For each layer in order: (kind, heads, its stack of weights, its
    index there, its kind's own stack or None, its rank there)."""
    kinds, nd = c["layer_types"], c["n_dense_layers"]
    out = []
    for i, (kind, heads) in enumerate(zip(kinds, c["heads"])):
        stack, mixers, first = (
            ("dense_layers", "dense_mixers", 0) if i < nd
            else ("layers", "mixers", nd))
        own = params.get(mixers)
        out.append((kind, heads, params[stack], i - first,
                    None if own is None else own[kind],
                    kinds[first:i].count(kind)))
    return out


def hidden(config, params, tokens):
    """tokens (B, S) -> (final-norm hidden states (B, S, D) float32, router
    statistics: the smallest router margin over the layers at each position
    (B, S), and the load-balance term, 0). ROW_GROUP sequences go through
    the layers at a time, so a probe of many prompts needs the memory of a
    few."""
    c = _sizes(config)
    kinds, heads = c.pop("layer_types"), c.pop("heads")
    c_items = tuple(sorted(c.items()))
    layers = _layers({**c, "layer_types": kinds, "heads": heads}, params)
    xs, margins = [], []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, tokens.shape[0], ROW_GROUP):
            rows = tokens[r0:r0 + ROW_GROUP]
            x = jnp.take(params["embed"], rows, axis=0).astype(F32)
            margin = jnp.full(rows.shape, jnp.inf, F32)
            for kind, h, stack, index, own, rank in layers:
                x, m = _layer_jit(c_items, kind, h, x, stack, index, own, rank)
                margin = jnp.minimum(margin, m)
            xs.append(_rms_norm(x, params["final_norm"].astype(F32), c["norm_eps"]))
            margins.append(margin)
    return jnp.concatenate(xs), {"margin": jnp.concatenate(margins),
                                 "aux": jnp.zeros((), F32)}


def logits(config, params, tokens):
    """tokens (B, S) -> logits (B, S, V) float32."""
    x, _ = hidden(config, params, tokens)
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(F32)


def greedy_path(config, params, prompts, steps: int):
    """prompts (B, P) -> (greedy tokens (B, steps), the logits at the `steps`
    positions that produced them (B, steps, V), and the router margin at
    those positions (B, steps)). One fixed sequence length, filled in a
    token at a time: position P-1+k sees only tokens before it, so the pad
    beyond does not reach it."""
    b, plen = prompts.shape
    seq = jnp.concatenate([prompts, jnp.zeros((b, steps), prompts.dtype)], axis=1)
    out_tokens, out_logits, out_margins = [], [], []
    for k in range(steps):
        x, stats = hidden(config, params, seq)
        with jax.default_matmul_precision("highest"):
            row = x[:, plen - 1 + k] @ params["lm_head"].astype(F32)
        tok = jnp.argmax(row, axis=-1).astype(prompts.dtype)
        out_tokens.append(tok)
        out_logits.append(row)
        out_margins.append(stats["margin"][:, plen - 1 + k])
        seq = seq.at[:, plen + k].set(tok)
    return (jnp.stack(out_tokens, axis=1), jnp.stack(out_logits, axis=1),
            jnp.stack(out_margins, axis=1))


def loss(config, params, batch, sample_rows: int = 0, sample_tail: int = 0):
    """The train step's objective on `batch` ({"inputs", "targets"} (B, S)):
    -> (cross-entropy mean, load-balance term (0: the router has none),
    sample), as `reference/mellum.py`'s. One row goes through at a time."""
    inputs, targets = batch["inputs"], batch["targets"]
    ce_sum = jnp.zeros((), F32)
    sample = {"logits": [], "margin": []}
    for r in range(inputs.shape[0]):
        x, stats = hidden(config, params, inputs[r:r + 1])
        with jax.default_matmul_precision("highest"):
            lg = x @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        ce_sum = ce_sum - jnp.sum(
            jnp.take_along_axis(logp, targets[r:r + 1, :, None], axis=-1))
        if r < sample_rows:
            sample["logits"].append(lg[0, -sample_tail:])
            sample["margin"].append(stats["margin"][0, -sample_tail:])
    sample = {k: jnp.stack(v) for k, v in sample.items() if v}
    return ce_sum / (inputs.shape[0] * inputs.shape[1]), jnp.zeros((), F32), sample


def check_logits(sys_logits, ref_logits, ref_margins) -> Dict[str, Any]:
    """The system's logits against the reference's over the positions that are
    not router near-ties, in reference sd: the median of the per-position
    root-mean-square error and the root-mean-square over all of them."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    keep = np.asarray(ref_margins, np.float32) >= ROUTER_TIE_MARGIN
    if not keep.any():
        return {"ok": False, "positions": 0}
    diff = (np.asarray(sys_logits, np.float32) - ref_logits)[keep]
    sd = float(ref_logits[keep].std())
    per_position = np.sqrt(np.mean(diff * diff, axis=-1)) / sd
    median = float(np.median(per_position))
    rms = float(np.sqrt(np.mean(diff * diff)) / sd)
    return {
        "median_error_sd": median, "median_tolerance_sd": LOGIT_MEDIAN_TOL,
        "rms_error_sd": rms, "rms_tolerance_sd": LOGIT_RMS_TOL,
        "positions": int(keep.sum()), "router_ties_skipped": int((~keep).sum()),
        "ok": median <= LOGIT_MEDIAN_TOL and rms <= LOGIT_RMS_TOL,
    }


def check_tokens(server_tokens, ref_tokens, ref_logits, ref_margins) -> Dict[str, Any]:
    """Hold the server's greedy tokens to the reference's logits: the share
    rule (see the module's note). A token is checked for as long as the
    server's earlier tokens follow the reference's own greedy path, and is
    within tolerance when its reference logit is within LOGIT_TOL sd (of that
    position's logits) of the reference's maximum. At least PASS_SHARE of the
    checked positions must be within, and at least MIN_CHECKED checked."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    ref_margins = np.asarray(ref_margins, np.float32)
    checked = followed = router_ties = 0
    gaps = []
    for i, row in enumerate(server_tokens):
        for k, tok in enumerate(row):
            lg = ref_logits[i, k]
            gaps.append(float((lg.max() - lg[int(tok)]) / lg.std()))
            checked += 1
            router_ties += bool(ref_margins[i, k] < ROUTER_TIE_MARGIN)
            if int(tok) != int(ref_tokens[i][k]):
                break
            followed += 1
    passed = sum(gap <= LOGIT_TOL for gap in gaps)
    share = passed / checked if checked else 0.0
    return {
        "checked": checked, "passed": passed, "pass_share": share,
        "required_share": PASS_SHARE, "followed_reference": followed,
        "router_near_ties": router_ties,
        "worst_gap_sd": max(gaps, default=0.0), "tolerance_sd": LOGIT_TOL,
        # how the rule would read at other tolerances: the next calibration's
        "outside_at_sd": {str(t): sum(gap > t for gap in gaps) for t in GAP_LADDER},
        "ok": checked >= MIN_CHECKED and share >= PASS_SHARE,
    }
