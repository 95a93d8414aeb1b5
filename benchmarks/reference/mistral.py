"""Plain reference of the Mistral / Mixtral decoder: float32 `jax.numpy`,
`default_matmul_precision("highest")`, no cache, no kernels, no batching
tricks, no expert capacity. It decides `correct` and the program cannot
change it.

Follows the published models (Mistral 7B, arXiv:2310.06825; Mixtral of
Experts, arXiv:2401.04088; the Hugging Face `modeling_mistral` /
`modeling_mixtral` files those point to):

- pre-norm blocks, RMSNorm in float32, eps from the config;
- grouped-query attention, causal, RoPE in the rotate-half form
  (`[x1 cos - x2 sin, x1 sin + x2 cos]` over the two halves of a head),
  theta from the config, no sliding window (null in both configs);
- SwiGLU MLP `down(silu(gate(x)) * up(x))`;
- Mixtral: softmax over all experts' router logits, top-k, renormalise the
  k weights, sum the chosen experts' SwiGLU outputs. Written as a dense loop
  over the experts with a zero weight where an expert was not chosen: every
  routed token is computed, none is dropped.

Departures, each for a reason:

- Weights come from the program's `transformer.init_params` tree (stacked
  per layer, `x @ w` layout) because the comparison is on the same seeded
  weights; only the layout is taken from it, no arithmetic.
- The load-balance term is the program's documented one (per layer:
  experts x sum_e mean router probability_e x share of tokens whose FIRST
  choice is e; summed over layers), not Hugging Face's (top-k mask over all
  layers' tokens at once). Mixtral's paper defines none; the coefficient is
  the published 0.02. PERF.md lists the difference under Open questions.
- One layer runs at a time, with that layer's weights cast to float32 on
  the way in, so the reference fits beside the bf16 weights on one chip.

Tolerances, written here with their reasons. With `init_params` weights the
logits at a position are close to standard normal (the final RMSNorm gives
unit RMS, head entries are N(0, 1/d)), so logit tolerances are in units of
the reference logits' standard deviation (sd). The served and trained model
is bf16 (weights and activations, float32 accumulation); the reference is
float32 throughout on the same bf16 weights.

LOGIT_RMS_TOL, where the system's logits can be read (the trainer's forward
on the chip; tests/test_reference.py on the CPU): root-mean-square logit
error over the compared positions <= 0.06 sd. Measured on the CPU at `tiny`
and `tiny-moe`, 2 and 8 layers: bf16 0.010-0.030; the same weights rounded
to e4m3 0.12-0.22; one expert's output zeroed 0.57-0.58; capacity factor
1.25 (dropped tokens) 0.23-0.29. 0.06 is twice the worst bf16 reading and
half the best 8-bit one. The first loss is no such test: at initialisation
the final norm rescales whatever the blocks did, and every one of those
faults moves the loss by under 0.02.

LOGIT_TOL, where only tokens can be read (the server returns text, the
engine token ids; neither returns logits): the reference logit of each
greedy token the engine returns must be within 0.15 sd of the reference's
maximum at that position. With random weights the largest logit changes on
rounding, so equality of tokens would be the wrong test. This catches what
moves logits by some tenths of an sd (a dropped expert, a wrong cache row,
a missing layer); an 8-bit copy is caught only sometimes, because a token
changes only where the two largest logits are closer than the error.
PERF.md lists `logprobs` on the server under Open questions.

ROUTER_TIE_MARGIN: an expert layer chooses its top k by router probability.
The router is float32 in both, but the bf16 model feeds it bf16 activations,
so where the k-th and (k+1)-th probabilities are within rounding the two may
choose different experts, and then that position's logits differ by far more
than any rounding tolerance without either being wrong (seen at `tiny-moe`
on the CPU: one flipped token in 192, logit error 2.4 sd). The reference
knows where that can happen: it reports, for every position, the smallest
log ratio between the last chosen and the first unchosen expert over the
layers. A position under 0.05 (five times the ~0.01 a bf16 input moves a
router logit) is left out of both comparisons; at least MIN_CHECKED token
positions must remain.

LOSS_TOL, AUX_TOL: the train step's first loss and load-balance term against
the reference's on the same batch, 0.02 absolute each (bf16 at `tiny` on the
CPU: 0.001 and 0.004). They catch a wrong objective (a missing aux term, a
shifted target), not a wrong layer: see LOGIT_RMS_TOL.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp

LOGIT_RMS_TOL = 0.06
LOGIT_TOL = 0.15
ROUTER_TIE_MARGIN = 0.05
MIN_CHECKED = 8
LOSS_TOL = 0.02
AUX_TOL = 0.02

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq  # (S, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(c: Dict[str, Any], x, p):
    b, s, _ = x.shape
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["d_model"] // c["n_heads"]
    xn = _rms_norm(x, p["attn_norm"], c["norm_eps"])
    q = _rope((xn @ p["wq"]).reshape(b, s, h, hd), c["rope_theta"])
    k = _rope((xn @ p["wk"]).reshape(b, s, kv, hd), c["rope_theta"])
    v = (xn @ p["wv"]).reshape(b, s, kv, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    rep = h // kv
    groups = []
    # One KV head and its `rep` query heads at a time: the (S, S) float32
    # scores of all heads at once would not fit beside a train state.
    for g in range(kv):
        qg = q[:, :, g * rep:(g + 1) * rep]                      # (B, S, rep, hd)
        scores = jnp.einsum("bqrd,bkd->brqk", qg, k[:, :, g]) * hd ** -0.5
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        groups.append(jnp.einsum("brqk,bkd->bqrd", probs, v[:, :, g]))
    out = jnp.concatenate(groups, axis=2).reshape(b, s, h * hd)
    return x + out @ p["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _mlp(c: Dict[str, Any], x, p):
    """-> (x, router probability sums (E,), first-choice counts (E,),
    router margin (B, S): log of the last chosen expert's probability over
    the first one left out, +inf for a dense layer)."""
    xn = _rms_norm(x, p["mlp_norm"], c["norm_eps"])
    n_experts, k = c.get("n_experts", 0), c.get("experts_per_token", 0)
    if not n_experts:
        out = _swiglu(xn, p["w_gate"].astype(F32), p["w_up"].astype(F32),
                      p["w_down"].astype(F32))
        return (x + out, jnp.zeros((0,), F32), jnp.zeros((0,), F32),
                jnp.full(x.shape[:2], jnp.inf, F32))
    probs = jax.nn.softmax(xn @ p["router"], axis=-1)          # (B, S, E)
    ranked, top_i = jax.lax.top_k(probs, min(k + 1, n_experts))
    margin = (jnp.log(ranked[..., k - 1]) - jnp.log(ranked[..., k])
              if n_experts > k else jnp.full(x.shape[:2], jnp.inf, F32))
    top_w, top_i = ranked[..., :k], top_i[..., :k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, n_experts, dtype=F32)        # (B, S, k, E)
    weight = jnp.einsum("bsk,bske->bse", top_w, chosen)
    out = jnp.zeros_like(x)
    for e in range(n_experts):
        y = _swiglu(xn, p["we_gate"][e].astype(F32), p["we_up"][e].astype(F32),
                    p["we_down"][e].astype(F32))
        out = out + weight[..., e:e + 1] * y
    return (x + out, jnp.sum(probs, axis=(0, 1)),
            jnp.sum(chosen[:, :, 0, :], axis=(0, 1)), margin)


def _layer(c_items, x, layers, index):
    """One block on `x`, with layer `index` of the stacked weights. The slice
    is taken inside the jitted function, next to the cast to float32, so no
    second copy of a layer's bf16 weights is ever held."""
    c = dict(c_items)
    p = jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False), layers)
    small = {k: p[k].astype(F32) for k in
             ("wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "router") if k in p}
    return _mlp(c, _attention(c, x, small), {**p, **small})


_layer_jit = jax.jit(_layer, static_argnums=0)


def _sizes(config) -> Dict[str, Any]:
    """The sizes the reference reads, from the program's ModelConfig or a
    dict of its fields; a dense model has no expert keys, which read as 0."""
    fields = config if isinstance(config, dict) else vars(config)
    return {k: fields.get(k, 0) for k in (
        "d_model", "n_heads", "n_kv_heads", "n_layers", "rope_theta", "norm_eps",
        "n_experts", "experts_per_token", "router_aux_coef",
    )}


def hidden(config, params, tokens):
    """tokens (B, S) -> (final-norm hidden states (B, S, D) float32, router
    statistics: probability sums (L, E), first-choice counts (L, E), and the
    smallest router margin over the layers at each position (B, S))."""
    c = _sizes(config)
    c_items = tuple(sorted(c.items()))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        prob_sums, first_counts = [], []
        margin = jnp.full(tokens.shape, jnp.inf, F32)
        for layer in range(c["n_layers"]):
            x, ps, fc, m = _layer_jit(c_items, x, params["layers"], layer)
            prob_sums.append(ps)
            first_counts.append(fc)
            margin = jnp.minimum(margin, m)
        x = _rms_norm(x, params["final_norm"].astype(F32), c["norm_eps"])
    return x, {"prob_sum": jnp.stack(prob_sums),
               "first_count": jnp.stack(first_counts), "margin": margin}


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
    -> (cross-entropy mean + router_aux_coef x aux, aux, sample). One row goes
    through at a time, so one row's float32 logits are all that is live.
    `sample` holds the logits and router margins of the last `sample_tail`
    positions of the first `sample_rows` rows, for `check_logits`."""
    c = _sizes(config)
    inputs, targets = batch["inputs"], batch["targets"]
    ce_sum = jnp.zeros((), F32)
    prob_sum = first_count = 0.0
    sample = {"logits": [], "margin": []}
    for r in range(inputs.shape[0]):
        x, stats = hidden(config, params, inputs[r:r + 1])
        with jax.default_matmul_precision("highest"):
            lg = x @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        ce_sum = ce_sum - jnp.sum(
            jnp.take_along_axis(logp, targets[r:r + 1, :, None], axis=-1))
        prob_sum = prob_sum + stats["prob_sum"]
        first_count = first_count + stats["first_count"]
        if r < sample_rows:
            sample["logits"].append(lg[0, -sample_tail:])
            sample["margin"].append(stats["margin"][0, -sample_tail:])
    sample = {k: jnp.stack(v) for k, v in sample.items() if v}
    n_tokens = inputs.shape[0] * inputs.shape[1]
    ce = ce_sum / n_tokens
    if not c["n_experts"]:
        return ce, jnp.zeros((), F32), sample
    aux = jnp.sum(c["n_experts"] * jnp.sum(
        (prob_sum / n_tokens) * (first_count / n_tokens), axis=-1))
    return ce + c["router_aux_coef"] * aux, aux, sample


def check_logits(sys_logits, ref_logits, ref_margins) -> Dict[str, Any]:
    """Root-mean-square error of the system's logits against the reference's,
    in reference sd, over the positions that are not router near-ties."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    keep = np.asarray(ref_margins, np.float32) >= ROUTER_TIE_MARGIN
    if not keep.any():
        return {"ok": False, "positions": 0}
    diff = (np.asarray(sys_logits, np.float32) - ref_logits)[keep]
    rms = float(np.sqrt(np.mean(diff * diff)) / ref_logits[keep].std())
    return {
        "rms_error_sd": rms, "tolerance_sd": LOGIT_RMS_TOL,
        "positions": int(keep.sum()), "router_ties_skipped": int((~keep).sum()),
        "ok": rms <= LOGIT_RMS_TOL,
    }


def check_tokens(server_tokens, ref_tokens, ref_logits, ref_margins) -> Dict[str, Any]:
    """Hold the server's greedy tokens to the reference's logits.

    A token is checked for as long as the server's earlier tokens follow the
    reference's own greedy path (only there does the reference have logits
    for the same context). It passes when its reference logit is within
    LOGIT_TOL standard deviations (of that position's logits) of the
    reference's maximum: with random weights the largest logit changes on
    rounding, so equality of tokens would be the wrong test. A position
    whose router margin is under ROUTER_TIE_MARGIN in some layer is not
    checked: there rounding may legitimately choose another expert."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    ref_margins = np.asarray(ref_margins, np.float32)
    checked = passed = followed = router_ties = 0
    worst = 0.0
    for i, row in enumerate(server_tokens):
        for k, tok in enumerate(row):
            if ref_margins[i, k] < ROUTER_TIE_MARGIN:
                router_ties += 1
            else:
                lg = ref_logits[i, k]
                gap = float((lg.max() - lg[int(tok)]) / lg.std())
                checked += 1
                worst = max(worst, gap)
                passed += gap <= LOGIT_TOL
            if int(tok) != int(ref_tokens[i][k]):
                break
            followed += 1
    return {
        "checked": checked, "passed": passed, "followed_reference": followed,
        "router_ties_skipped": router_ties,
        "worst_gap_sd": worst, "tolerance_sd": LOGIT_TOL,
        "ok": checked >= MIN_CHECKED and passed == checked,
    }
