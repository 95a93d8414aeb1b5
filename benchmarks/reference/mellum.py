"""Plain reference of the `mellum` decoder (JetBrains Mellum2-12B-A2.5B):
float32 `jax.numpy`, `default_matmul_precision("highest")`, no cache, no
kernels, no periods, no expert capacity. It decides `correct` and the program
cannot change it.

Follows the published configuration (JetBrains/Mellum2-12B-A2.5B-Instruct
`config.json`, `model_type` mellum) and, for the scaled rotary embedding, the
YaRN paper (arXiv:2309.00071) as `transformers` computes it
(`_compute_yarn_parameters`). One layer, with `x` the block input:

1. `h = RMSNorm(x)`; `q = h Wq -> (S, H, hd)`, `k = h Wk`, `v = h Wv ->
   (S, KV, hd)`, no bias, no query/key norm. `hd` is the published
   `head_dim` (128), NOT `hidden_size / num_attention_heads` (2,304 / 32 = 72):
   `Wq` is hidden x H hd, `Wo` is H hd x hidden.
2. RoPE (rotate-half) on `q` and `k`, BY THE LAYER'S KIND. A
   `sliding_attention` layer: `inv_freq_i = theta^(-2i / hd)`. A
   `full_attention` layer, YaRN: `extra = inv_freq`, `inter = inv_freq /
   factor`, `low, high = floor, ceil` of `hd ln(orig / (beta 2 pi)) / (2 ln
   theta)` for `beta = beta_fast, beta_slow`, `ramp_i = clip((i - low) / (high
   - low), 0, 1)`, `inv_freq' = inter ramp + extra (1 - ramp)`; `cos` and
   `sin` are multiplied by `attention_factor`. Computed here in float64 from
   the published group, independently of `config.RopeParams`.
3. Scores `q k^T / sqrt(hd)`, H / KV query heads a KV head; key `j` is visible
   to query `i` iff `j <= i` and, in a sliding layer, `i - j <
   sliding_window`. Softmax, `o = P v`, `x += o Wo`. Scores are taken a block
   of query rows at a time so the probe's 4,100 positions fit.
4. `h2 = RMSNorm(x)`; `p = softmax(h2 W_r)` over all experts (router float32);
   the k largest; `w = p_top / sum p_top` (`norm_topk_prob`); `x += sum_e w_e
   down_e(silu(gate_e h2) * up_e h2)`. A loop over ALL experts with a zero
   weight where an expert was not chosen: every routed token is computed, none
   is dropped. No shared expert, no dense layer (`mlp_layer_types` all sparse;
   `intermediate_size` is read by nothing).
5. Final RMSNorm, untied head.

Departures, each for a reason:

- Weights come from the program's `transformer.init_params` tree (one stack
  `layers`, `x @ w` layout) because the comparison is on the same seeded
  weights; only the layout is taken. The layer's KIND is read here from
  `layer_types[index]`, one layer at a time: the reference has no periods.
- RoPE pairs column i with column i + hd/2 (rotate-half), the form
  `transformers` uses for this family; `model.json` lists it under `assumed`.
- The multi-token-prediction head of the model card is not in `config.json`
  and is not run. YaRN's `truncate` is the library's default (true).
- The load-balance term is the program's documented one, as in
  `reference/mistral.py` (no train cell uses it here).
- One layer runs at a time with its weights cast to float32 on the way in,
  one expert at a time inside it, so the reference fits beside the bf16
  weights on one chip.

Tolerances. With `init_params` weights the logits at a position are close to
standard normal, so tolerances are in units of the reference logits' standard
deviation (sd). The served model is bf16 (weights and activations, float32
accumulation, float32 router); the reference is float32 on the same weights.

ROUTING FLIPS at 64 experts top-8 are frequent and SMALL. Where the 8th and
9th router probabilities of a layer are within rounding, the bf16 program (bf16
activations into a float32 router) may pick another expert; the smallest log
ratio between them over 8 layers is under 0.01 at 60-65% of the positions. But
a flipped expert is the 8th of eight with renormalised weights, about a tenth
of the layer's output, so a flip moves the logits far less than at top-2 of 8
or top-4 of 64 beside a shared expert (`reference/glm4_moe_lite.py`: bf16 RMS
0.15-0.17 sd there, 0.04-0.05 here).

ROUTER_TIE_MARGIN = 0.01, the margin rule of `reference/mistral.py`: the
reference reports for every position the smallest log ratio between the last
chosen and the first unchosen expert over the layers, and `check_logits`
leaves positions under the margin out (0.01 is what a bf16 input moves a
router logit by; at 0.05, `mistral.py`'s, 1% of the positions are left).

LOGIT_MEDIAN_TOL = 0.08 sd and LOGIT_RMS_TOL = 0.1 sd, where logits can be
read (the CPU tests: `forward`, and chunked prefill then decode through the
paged pool): over the kept positions, the MEDIAN of the per-position RMS logit
error and the RMS over all of them. Both must hold. Readings (CPU, PR 31; the
`tiny-window` preset widened to 64 experts top-8 with no token dropped, hidden
256, vocabulary 8,192, 8 layers wwwf wwwf, window 16, YaRN factor 16 over 32
positions; 8 rows x 128 positions, seeds 1-4), median / RMS: the bf16 program
0.031-0.036 / 0.043-0.047; weights rounded to e4m3 0.172 / 0.177-0.178; plain
RoPE on the full layers 0.227-0.245 / 0.246-0.257; every layer full 1.08-1.12
/ 1.03-1.06. 0.08 is 2.2 times the worst bf16 median and under half the e4m3
one; 0.1 is 2.1 times the worst bf16 RMS and 0.56 of e4m3's. At `tiny-window`
itself (8 experts top-3: one flip is a third of a layer) the bf16 RMS over
fifty positions reads 0.02-0.11, so the tier-1 tests give it twice the room.

LOGIT_TOL = 0.05 sd, PASS_SHARE = 0.93, MIN_CHECKED = 8, where only tokens can
be read (the engine's probe): the SHARE RULE of `reference/glm4_moe_lite.py`
with this model's own numbers. A greedy token the engine returns is within
tolerance when its reference logit is within LOGIT_TOL sd (of that position's
logits) of the reference's maximum; at least PASS_SHARE of the checked
positions must be, and at least MIN_CHECKED must have been checked. A token is
checked for as long as the engine's earlier tokens follow the reference's own
greedy path, so a row has at most ONE position outside (its last), and what
the rule counts is rows: the probe is 32 rows x 4 tokens (`cuts/serve.json`).
ISSUE 31 asked for the latent cell's numbers (0.15 sd, half); at this model's
widths they tell nothing apart. Readings ON THE CHIP at the cell's widths
(my chip runs, PR 31; 32 prompts x 2,048 tokens x 4 new tokens; rows that leave
the reference's path | checked positions outside 0.05 sd | outside 0.15 sd):
the bf16 engine, seven seeds: 3-9 of 32 rows | 0-3 of 111-127 (0-2.6%) | 0-1;
the plain reference fed e4m3-rounded weights (rounded in float32 arithmetic:
`astype` under `jit` moves nothing on the v5e), two seeds: 26, 19 rows | 20 of
67, 13 of 82 (29.9%, 15.9%) | 13, 5 (19.4%, 6.1%: the second PASSES the latent
cell's rule); plain RoPE on the full layers: 16 rows | 13 of 98 (13.3%) | 4
(4.1%: passes it too); every layer full: 31 rows | 30 of 42 (71%) | 29. With
at most one position a row outside, 7% of ~120 checked positions is 8 rows: a
bf16 engine whose rows end outside with probability 0.05 (11 of 224 rows
did) fails with probability ~1e-5, an e4m3 copy (0.41-0.62) passes with 5% or
less. On the CPU
at the widening above (32-64 rows, seeds 1-4): bf16 3.5% outside, e4m3 32%,
plain RoPE 37%, every layer full 100% (`tests/test_reference_mellum.py`).
`check_tokens` also reports how many checked positions lie outside each
tolerance of GAP_LADDER, so the next calibration needs no new run.
"""

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# The readings behind each are in the module's note.
LOGIT_MEDIAN_TOL = 0.08
LOGIT_RMS_TOL = 0.1
LOGIT_TOL = 0.05
PASS_SHARE = 0.93
ROUTER_TIE_MARGIN = 0.01
MIN_CHECKED = 8
LOSS_TOL = 0.02
AUX_TOL = 0.02
GAP_LADDER = (0.02, 0.05, 0.075, 0.1, 0.15)
ROW_BLOCK = 256  # query rows whose scores are live at once
ROW_GROUP = 8    # sequences that go through the layers together

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope_frequencies(group: Dict[str, Any], dim: int):
    """(inverse frequencies (dim / 2,) as floats, the factor on cos and sin)
    of one published `rope_parameters` group, in float64."""
    theta = float(group["rope_theta"])
    extra = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if group.get("rope_type", "default") == "default":
        return extra, 1.0
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
    return out, float(scale)


def _rope(x, inv_freq, scale):
    """x: (B, S, H, d), positions 0..S-1, rotate-half pairing."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(c: Dict[str, Any], kind: str, x, p):
    b, s, _ = x.shape
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    inv_freq, scale = rope_frequencies(dict(dict(c["rope"])[kind]), hd)
    xn = _rms_norm(x, p["attn_norm"], c["norm_eps"])
    q = _rope((xn @ p["wq"]).reshape(b, s, h, hd), inv_freq, scale)
    k = _rope((xn @ p["wk"]).reshape(b, s, kv, hd), inv_freq, scale)
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
    out = jnp.concatenate(rows, axis=1).reshape(b, s, h * hd)
    return x + out @ p["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _mlp(c: Dict[str, Any], x, p):
    """-> (x, router margin (B, S): the log ratio between the k-th and the
    (k+1)-th router probability, and what the load-balance term is made of:
    the router probabilities and the first choices, each summed over the
    tokens, (2, E))."""
    xn = _rms_norm(x, p["mlp_norm"], c["norm_eps"])
    n_experts, k = c["n_experts"], c["experts_per_token"]
    probs = jax.nn.softmax(xn @ p["router"], axis=-1)            # (B, S, E)
    ranked, top_i = jax.lax.top_k(probs, min(k + 1, n_experts))
    margin = (jnp.log(ranked[..., k - 1]) - jnp.log(ranked[..., k])
              if n_experts > k else jnp.full(x.shape[:2], jnp.inf, F32))
    chosen = jax.nn.one_hot(top_i[..., :k], n_experts, dtype=F32)  # (B, S, k, E)
    weight = jnp.sum(chosen, axis=2) * probs                     # (B, S, E)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(out, e):
        gate, up, down, w = e
        y = _swiglu(xn, gate.astype(F32), up.astype(F32), down.astype(F32))
        return out + w[..., None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p["we_gate"], p["we_up"], p["we_down"], jnp.moveaxis(weight, -1, 0)))
    sums = jnp.stack([jnp.sum(probs, axis=(0, 1)), jnp.sum(chosen[:, :, 0], axis=(0, 1))])
    return x + out, margin, sums


SMALL = ("wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "router")


def _layer(c_items, kind, x, layers, index):
    """One `kind` block on `x`, with layer `index` of the stack of weights.
    The slice is taken inside the jitted function, next to the cast to
    float32, so no second copy of a layer's bf16 weights is ever held."""
    c = dict(c_items)
    p = jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False), layers)
    small = {k: p[k].astype(F32) for k in SMALL}
    return _mlp(c, _attention(c, kind, x, small), {**p, **small})


_layer_jit = jax.jit(_layer, static_argnums=(0, 1))


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
    c = {k: f[k] for k in ("d_model", "n_heads", "n_kv_heads", "n_layers",
                           "norm_eps", "n_experts", "experts_per_token")}
    c["head_dim"] = f.get("head_size") or f["d_model"] // f["n_heads"]
    c["sliding_window"] = int(f.get("sliding_window") or 0)
    kinds = tuple(f.get("layer_types") or ())[: f["n_layers"]]
    c["layer_types"] = kinds or (FULL,) * f["n_layers"]
    ropes = dict(f.get("rope_parameters") or ())
    plain = {"rope_type": "default", "rope_theta": f.get("rope_theta", 500000.0)}
    c["rope"] = tuple((kind, _group(ropes.get(kind, plain)))
                      for kind in (FULL, SLIDING))
    return c


def hidden(config, params, tokens):
    """tokens (B, S) -> (final-norm hidden states (B, S, D) float32, router
    statistics: the smallest router margin over the layers at each position
    (B, S), and the load-balance term summed over the layers). ROW_GROUP
    sequences go through the layers at a time, so a probe of many prompts
    needs the memory of a few."""
    c = _sizes(config)
    kinds = c.pop("layer_types")
    c_items = tuple(sorted(c.items()))
    xs, margins = [], []
    sums = jnp.zeros((len(kinds), 2, c["n_experts"]), F32)
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, tokens.shape[0], ROW_GROUP):
            rows = tokens[r0:r0 + ROW_GROUP]
            x = jnp.take(params["embed"], rows, axis=0).astype(F32)
            margin = jnp.full(rows.shape, jnp.inf, F32)
            for layer, kind in enumerate(kinds):
                x, m, s = _layer_jit(c_items, kind, x, params["layers"], layer)
                margin = jnp.minimum(margin, m)
                sums = sums.at[layer].add(s)
            xs.append(_rms_norm(x, params["final_norm"].astype(F32), c["norm_eps"]))
            margins.append(margin)
    # Per layer: experts x sum_e mean router probability_e x share of tokens
    # whose FIRST choice is e, the means over all the batch's tokens.
    means = sums / tokens.size
    aux = c["n_experts"] * jnp.sum(means[:, 0] * means[:, 1])
    return jnp.concatenate(xs), {"margin": jnp.concatenate(margins), "aux": aux}


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
    -> (cross-entropy mean, load-balance term, sample), as
    `reference/mistral.py`'s. One row goes through at a time; the
    load-balance term is a mean over ALL rows' tokens a layer, so it is taken
    from one pass over the whole batch's router statistics."""
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
    _, stats = hidden(config, params, inputs)
    return ce_sum / (inputs.shape[0] * inputs.shape[1]), stats["aux"], sample


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
