"""Plain reference of the `glm4_moe_lite` decoder (GLM-4.7-Flash): float32
`jax.numpy`, `default_matmul_precision("highest")`, no cache, no absorption,
no kernels, no expert capacity. It decides `correct` and the program cannot
change it.

Follows the published configuration (zai-org/GLM-4.7-Flash `config.json`,
`model_type` glm4_moe_lite) and the DeepSeek-V2/V3 description of its two
mechanisms (arXiv:2405.04434 section 2.1, multi-head latent attention;
arXiv:2412.19437 section 2.1.2, auxiliary-loss-free routing). With `x` the
block input, `h = RMSNorm(x)`, H heads:

- latent attention, EXPANDED: `c_q = RMSNorm(h W_dq)`; `q = c_q W_uq`, per
  head `[q_nope | q_rope]`, `q_rope <- RoPE(q_rope)`; `[c_kv | k_rope] = h
  W_dkv`, `c_kv <- RMSNorm(c_kv)`, `k_rope <- RoPE(k_rope)` (one per token,
  shared by all heads); `[k_nope | v]_head = c_kv W_ukv`; `k = [k_nope |
  k_rope]`; scores `q.k / sqrt(nope + rope)`, causal softmax, `o_head = sum p
  v`; `x += concat(o) W_o`. Full keys and values of every head are built for
  every position; nothing is cached and nothing is folded into the query.
  Scores are taken a block of query rows at a time so 8,192 positions fit.
- the first `n_dense_layers` blocks: SwiGLU `down(silu(gate(h)) * up(h))`;
- the expert blocks: `s = sigmoid(h W_g)` (router float32); the k largest of
  `s + b` are chosen (`b` the selection bias; `n_group` = `topk_group` = 1,
  so no group limit); weights `w_i = scaling * s_i / sum_chosen s_j`: the
  bias chooses and does not weigh; `y = sum w_i E_i(h) + E_shared(h)`, every
  E a SwiGLU. Written as a loop over ALL experts with a zero weight where an
  expert was not chosen: every routed token is computed, none is dropped.
- RMSNorm in float32, eps from the config; RoPE theta from the config, every
  rope column rotates (`partial_rotary_factor` 1), no scaling.

Departures, each for a reason:

- Weights come from the program's `transformer.init_params` tree (two
  stacks, `dense_layers` then `layers`, `x @ w` layout) because the
  comparison is on the same seeded weights; only the layout is taken.
- RoPE pairs column i with column i + d/2 (rotate-half), where the published
  checkpoints interleave (2i, 2i+1): a fixed permutation of W_uq / W_dkv
  columns, which seeded weights cannot tell apart. `model.json` lists it
  under `assumed`.
- The multi-token-prediction block (`num_nextn_predict_layers` 1) is not
  run: the published forward pass of the main layers never reads it.
- The router has no load-balance term (`noaux_tc`): `loss` returns aux 0.
- One layer runs at a time with its weights cast to float32 on the way in,
  one expert at a time inside it, so the reference fits beside the bf16
  weights on one chip.

Tolerances. With `init_params` weights the logits at a position are close to
standard normal, so tolerances are in units of the reference logits' standard
deviation (sd). The served model is bf16 (weights and activations, float32
accumulation, float32 router); the reference is float32 on the same weights.

What is different from `reference/mistral.py`, and why: ROUTING FLIPS ARE
EVERYWHERE. An expert layer picks its k largest `s + b`; where the k-th and
the (k+1)-th are within rounding, the bf16 program (bf16 activations into a
float32 router) may pick another expert, and that position's logits then
differ by far more than rounding without either being wrong. With 8 experts
top-2 that is one position in a hundred; with 64 experts top-4 over 6 expert
layers the 4th and 5th scores of a layer are about 0.018 apart, and measured
(CPU, PR 27: `tiny-latent` widened to 64 experts top-4, 1 + 6 layers, hidden
256, vocabulary 8,192, 3 seeds x 512 positions) 81% of the positions have a
gap under 0.005 in some layer, the bf16 program's logits read RMS 0.19-0.25 sd
against the reference over all positions, and 15-20% of its greedy tokens are
more than 0.15 sd under the reference's maximum. No margin leaves both enough
positions and no flips (at 0.005, 19% are left and 2-6% of those still flip).
So the margin rule is kept where it pays (logits), and both comparisons use a
statistic that a minority of flipped positions cannot move and a wrong model
moves at every position:

ROUTER_TIE_MARGIN = 0.002, the margin rule of `reference/mistral.py` on
`s + b`: the reference reports for every position the smallest gap
`(s + b)_k - (s + b)_{k+1}` over the expert layers, and `check_logits` leaves
positions under the margin out. A sigmoid's slope is at most 1/4 and a bf16
input moves a router logit by about 0.01, so a score moves by up to 0.0025;
0.002 leaves out the likeliest flips (half the positions at 64 experts, a
twentieth at `tiny-latent`) and still leaves many.

LOGIT_MEDIAN_TOL = 0.09 sd and LOGIT_RMS_TOL = 0.2 sd, where logits can be
read (CPU tests; a trainer's forward): over the kept positions, the MEDIAN of
the per-position RMS logit error, which flips in a minority of positions
cannot move, and the RMS over all of them, which a fault in a minority of
positions does move. Both must hold. Readings (median / RMS), bf16 against
faults injected into the bf16 program, lowest to highest over the seeds. At
`tiny-latent` (8 experts top-2, 1 + 2 layers, 4 seeds, a selection bias of sd
0.1): bf16 0.014-0.016 / 0.045-0.064; weights rounded to e4m3 0.185-0.197 /
0.32-0.33; one expert zeroed 0.037-0.28 / 0.27-0.42 (the RMS catches it);
the shared expert dropped 0.68-0.70; the selection bias ignored 0.44-0.60;
`routed_scaling_factor` left out 0.34-0.36; the chosen weights not
renormalised 0.25-0.30; every cache row read one position late 0.57-0.62;
capacity factor 1.25 (dropped tokens) 0.28-0.34. At the 64-expert widening (3
seeds): bf16 0.041-0.053 / 0.154-0.169 (the flips that the margin leaves);
e4m3 0.45-0.49 / 0.48-0.52; the shared expert dropped 0.93-0.99; the bias
ignored 0.71-0.78; scaling left out 0.47-0.50; a cache row late 0.79-0.96;
one of 64 experts zeroed 0.076-0.12 / 0.18-0.23: at that size a single
expert touches a third of the positions and is caught only sometimes. 0.09
is 1.7 times the worst bf16 median and half the smallest e4m3 one; 0.2 is
1.2 times the worst bf16 RMS at 64 experts and three quarters of the
smallest one-expert reading at `tiny-latent`.

LOGIT_TOL = 0.15 sd and PASS_SHARE = 0.5, where only tokens can be read (the
engine's probe): a greedy token the engine
returns is within tolerance when its reference logit is within 0.15 sd of the
reference's maximum at that position (with random weights the largest logit
changes on rounding, so equality of tokens would be the wrong test), and at
least half of the checked positions must be. A token is checked for as long
as the engine's earlier tokens follow the reference's own greedy path. No
position is left out here: at 64 experts the margin rule would leave a
16-position probe with too few. Readings at the 64-expert widening, share of
positions OUTSIDE 0.15 sd: bf16 15-20%; e4m3 weights 61-69%; one of 64
experts zeroed 22-29% (not told apart by tokens: it is the logits' test);
the shared expert dropped 94-96%; scaling left out 61-70%; a cache row late
90-96%. With the probe's ~26 checked positions a bf16 engine at 20% outside
fails the half with probability under 1e-4 and an e4m3 copy at 65% passes it
with probability 6%. MIN_CHECKED = 8 positions must have been checked.
`test_tokens_tell_the_precision_below_apart_at_64_experts` sends the three
copies through `check_tokens` at that widening; the readings on the chip at
the cell's own widths (the engine's, and this reference fed e4m3-rounded
weights in the engine's place) are in PERF.md section 6, PR 27. A single
expert's fault is caught by no serving cell's number: tokens are all the
server returns.

LOSS_TOL, AUX_TOL: as `reference/mistral.py` (no train cell uses them yet).
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp

LOGIT_MEDIAN_TOL = 0.09
LOGIT_RMS_TOL = 0.2
LOGIT_TOL = 0.15
PASS_SHARE = 0.5
ROUTER_TIE_MARGIN = 0.002
MIN_CHECKED = 8
LOSS_TOL = 0.02
AUX_TOL = 0.02
ROW_BLOCK = 512  # query rows whose scores are live at once

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x: (B, S, H, d), positions 0..S-1, rotate-half pairing."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq  # (S, d/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(c: Dict[str, Any], x, p):
    b, s, _ = x.shape
    h, kvr = c["n_heads"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    xn = _rms_norm(x, p["attn_norm"], c["norm_eps"])
    cq = _rms_norm(xn @ p["wq_a"], p["q_norm"], c["norm_eps"])
    q = (cq @ p["wq_b"]).reshape(b, s, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], c["rope_theta"])], axis=-1)
    kv = xn @ p["wkv_a"]
    c_kv = _rms_norm(kv[..., :kvr], p["kv_norm"], c["norm_eps"])
    k_rope = _rope(kv[:, :, None, kvr:], c["rope_theta"])       # (B, S, 1, rope)
    up = (c_kv @ p["wkv_b"]).reshape(b, s, h, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rope))], axis=-1)
    v = up[..., nope:]
    key_pos = jnp.arange(s)
    rows = []
    # A block of query rows at a time: the (H, S, S) float32 scores of a
    # long prompt would not fit beside the weights.
    for r0 in range(0, s, ROW_BLOCK):
        qb = q[:, r0:r0 + ROW_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * (nope + rope) ** -0.5
        causal = key_pos[None, :] <= (r0 + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        rows.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    out = jnp.concatenate(rows, axis=1).reshape(b, s, h * vd)
    return x + out @ p["wo"]


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
    scores = jax.nn.sigmoid(xn @ p["router"])                    # (B, S, E)
    choose = scores + p["router_bias"]
    ranked, top_i = jax.lax.top_k(choose, min(k + 1, n_experts))
    margin = (ranked[..., k - 1] - ranked[..., k]
              if n_experts > k else jnp.full(x.shape[:2], jnp.inf, F32))
    chosen = jax.nn.one_hot(top_i[..., :k], n_experts, dtype=F32)  # (B, S, k, E)
    weight = jnp.sum(chosen, axis=2) * scores                    # (B, S, E)
    weight = c["routed_scaling"] * weight / jnp.sum(weight, axis=-1, keepdims=True)

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


SMALL = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
         "attn_norm", "mlp_norm", "router", "router_bias")


def _layer(c_items, x, layers, index):
    """One block on `x`, with layer `index` of one stack of weights. The
    slice is taken inside the jitted function, next to the cast to float32,
    so no second copy of a layer's bf16 weights is ever held."""
    c = dict(c_items)
    p = jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False), layers)
    small = {k: p[k].astype(F32) for k in SMALL if k in p}
    return _mlp(c, _attention(c, x, small), {**p, **small})


_layer_jit = jax.jit(_layer, static_argnums=0)


def _sizes(config) -> Dict[str, Any]:
    fields = config if isinstance(config, dict) else vars(config)
    return {k: fields[k] for k in (
        "d_model", "n_heads", "n_layers", "rope_theta", "norm_eps", "n_experts",
        "experts_per_token", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_dense_layers", "n_shared_experts",
        "routed_scaling",
    )}


def hidden(config, params, tokens):
    """tokens (B, S) -> (final-norm hidden states (B, S, D) float32, router
    statistics: the smallest router margin over the expert layers at each
    position (B, S))."""
    c = _sizes(config)
    c_items = tuple(sorted(c.items()))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        margin = jnp.full(tokens.shape, jnp.inf, F32)
        for name in ("dense_layers", "layers"):
            stack = params.get(name)
            if stack is None:
                continue
            for layer in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
                x, m = _layer_jit(c_items, x, stack, layer)
                margin = jnp.minimum(margin, m)
        x = _rms_norm(x, params["final_norm"].astype(F32), c["norm_eps"])
    return x, {"margin": margin}


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
    -> (cross-entropy mean, aux = 0: an aux-loss-free router, sample). One
    row goes through at a time. `sample` holds the logits and router margins
    of the last `sample_tail` positions of the first `sample_rows` rows, for
    `check_logits`."""
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
    ce = ce_sum / (inputs.shape[0] * inputs.shape[1])
    return ce, jnp.zeros((), F32), sample


def check_logits(sys_logits, ref_logits, ref_margins) -> Dict[str, Any]:
    """The system's logits against the reference's over the positions that are
    not router near-ties, in reference sd: the median of the per-position
    root-mean-square error and the root-mean-square over all of them (see
    LOGIT_MEDIAN_TOL for why both)."""
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
    """Hold the server's greedy tokens to the reference's logits. A token is
    checked for as long as the server's earlier tokens follow the reference's
    own greedy path (only there does the reference have logits for the same
    context), and is within tolerance when its reference logit is within
    LOGIT_TOL sd (of that position's logits) of the reference's maximum. At
    least PASS_SHARE of the checked positions must be within tolerance, and
    at least MIN_CHECKED must have been checked. Positions whose router
    margin is under ROUTER_TIE_MARGIN are counted, not left out (see the
    module's note on routing flips)."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    ref_margins = np.asarray(ref_margins, np.float32)
    checked = passed = followed = router_ties = 0
    worst = 0.0
    for i, row in enumerate(server_tokens):
        for k, tok in enumerate(row):
            lg = ref_logits[i, k]
            gap = float((lg.max() - lg[int(tok)]) / lg.std())
            checked += 1
            router_ties += bool(ref_margins[i, k] < ROUTER_TIE_MARGIN)
            worst = max(worst, gap)
            passed += gap <= LOGIT_TOL
            if int(tok) != int(ref_tokens[i][k]):
                break
            followed += 1
    share = passed / checked if checked else 0.0
    return {
        "checked": checked, "passed": passed, "pass_share": share,
        "required_share": PASS_SHARE, "followed_reference": followed,
        "router_near_ties": router_ties,
        "worst_gap_sd": worst, "tolerance_sd": LOGIT_TOL,
        "ok": checked >= MIN_CHECKED and share >= PASS_SHARE,
    }
