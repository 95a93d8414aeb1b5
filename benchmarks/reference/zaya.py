"""Plain reference of the `zaya` decoder (Zyphra ZAYA1-8B): float32
`jax.numpy`, `default_matmul_precision("highest")`, no cache, no kernels, no
tails, no expert capacity, no sorting. It decides `correct` and the program
cannot change it.

Follows the published configuration (Zyphra/ZAYA1-8B `config.json`,
`model_type` zaya), which names its mechanisms by model type only; two rows of
the same family spell them out (ZAYA1-VL-8B, the same 40 x 2,048 language
model: `cca` true, `zaya_mlp_expansion` 256 = `router_hidden_size`,
`zaya_use_eda` true, `zaya_use_mod` true, `scale_residual_merge` true;
ZAYA1-base the same keys), and the forms below follow the CCA paper
(Figliolia et al., Compressed Convolutional Attention, arXiv:2510.04476) and
Zyphra's ZAYA1 report. Every layer is `hybrid`: one CCA sublayer, then one
expert sublayer. H = 8 query heads and KV = 2 key heads of hd = 128 (1,024 and
256 values: the compressed latent, not the hidden 2,048); `x` is the block
input (S, 2,048):

1. CCA. `n = RMSNorm(x)`; `q~ = n Wq` (H hd), `k~ = n Wk` (KV hd), no bias.
   - VALUE SHIFT: `v_t = [n_t Wv[:, first half of each KV head] ; n_(t-1)
     Wv[:, second half]]`: half of each KV head's 128 channels from this
     token, half from the one before (zero before position 0). ASSUMED: the
     split is inside each head; the alternative is one whole KV head shifted.
   - TWO CAUSAL CONVOLUTIONS over time on `u = [q~ ; k~]` (every query head,
     then every key head: 1,280 channels, 10 groups of 128), zero before
     position 0: `c1_t = a_0 * u_t + a_1 * u_(t-1)` (depthwise, `cca_time0`
     = 2 taps), then `c2_t = B_0^(g) c1_t + B_1^(g) c1_(t-1)` (`cca_time1` = 2
     taps, a 128 x 128 matrix a tap and group g, mixing channels inside a
     head), no bias. ASSUMED: the two apply in this order, both act on queries
     and keys, tap j multiplies the input j tokens back.
   - QK-MEAN: `q^h = c2^(q,h) + (q~^h + k~^(g(h))) / 2`, `k^g = c2^(k,g) +
     (mean_(h in g) q~^h + k~^g) / 2`, g(h) = floor(h / (H / KV)), the
     grouped-query pairing. ASSUMED: the form of the mean.
   - L2 NORM: `q <- tau_h sqrt(hd) q / |q|`, `k <- k / |k|` a head, tau a
     learned scalar a query head. ASSUMED.
   - RoPE on the FIRST 64 of 128 values (`partial_rotary_factor` 0.5,
     rotate-half pairing inside them, theta 5,000,000 from
     `rope_parameters.hybrid`); the rest as they are.
   - Causal GQA softmax attention, scores `q k^T / sqrt(hd)` (= tau_h times
     the cosine), 4 query heads a KV head; `attn = concat_h(o^h) Wo` (1,024
     -> 2,048).
   - RESIDUAL MERGE: `x' = alpha * x + beta * attn`, alpha and beta learned a
     channel (`res_attn` rows 0 and 1), 1 at initialisation. ASSUMED: this
     form of `scale_residual_merge`.
2. The expert sublayer. `n' = RMSNorm(x')`.
   - ROUTER WITH EDA (exponential depth averaging): `r_l = n' W_down` (2,048
     -> 256), `r^_l = r_l + gamma_l r^_(l-1)`, carried from layer to layer at
     each position, `r^` before the first layer zero (so `r^_first =
     r_first`). ASSUMED: gamma a learned scalar a layer.
   - ROUTER MLP: `z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r^_l)))`, widths 256 ->
     256 -> 256 -> 17, gelu the exact (erf) form, no bias. ASSUMED: the
     depth and the activations.
   - MoD: the 17 logits are the 16 experts and one SKIP. `p = softmax(z)`,
     `e* = argmax(z + b)`, the selection bias b zero. `y = p_(e*)
     SwiGLU_(e*)(n')` for `e* < 16`, else `y = 0` (top-1: the weight is the
     score itself, not renormalised).
   - `x'' = alpha' * x' + beta' * y` (`res_mlp`).
3. Final RMSNorm, the head tied to the embedding: `logits = RMSNorm(x_L)
   E^T`.

Departures, each for a reason:

- Weights come from the program's `transformer.init_params` tree (`x @ w`
  layout, one `layers` stack; `wv`'s columns are KV heads of [the half kept,
  the half shifted]; `cca_conv0` (taps, 1,280), `cca_conv1` (taps, 10, 128,
  128), `cca_temp` (H,), `router_down`, `router_gamma`, `router_norm`,
  `router_w1`, `router_w2`, `router` (the router MLP's last layer, to the 17
  scores), `res_attn` / `res_mlp` (2, hidden)) because the comparison is on
  the same seeded weights; only the layout is taken.
- The program rounds `c1` to bf16 where it computes it, so that a chunk
  boundary (its tail keeps `c1` in bf16) changes nothing; this file keeps
  float32 throughout.
- No load-balance term; `loss` returns aux 0 (no train cell uses it).
- One layer runs at a time with its weights cast to float32 on the way in,
  one expert at a time inside it, ROW_GROUP sequences at a time, and the
  tied head a slice of the vocabulary at a time (`_head_stats`), so the
  reference fits beside 9.4 GB of bf16 weights on one chip, and the probe's
  check beside the server's pool too.
- `hidden(..., without=...)` runs the block with ONE mechanism taken out
  (`WITHOUT`): "conv" (c2 = u: no mixing over time), "value_shift" (v = n
  Wv), "eda" (gamma = 0) or "skip" (the 17th logit at minus infinity). They
  are the faults the tolerances below must catch, for their tests and the
  chip's calibration; `correct` runs none of them.

Tolerances. With `init_params` weights the logits at a position are close to
standard normal, so tolerances are in units of the reference logits' standard
deviation (sd). The served model is bf16 (weights and activations, float32
accumulation, router, convolution outputs before rounding, temperatures and
residual scales); the reference is float32 on the same weights.

ROUTING FLIPS. Top-1 over 17 choices flips a whole layer's expert, or its
skip, on a near-tie: where the two best of a layer's 17 probabilities lie
within what a bf16 input moves them by, the program may take the other one
and a position's logits move by most of an expert's output. ROUTER_TIE_MARGIN
leaves out the positions where a layer's two best probabilities are closer
than 0.002 (`check_logits`); `check_tokens` keeps them, and the SHARE RULE is
sized with them in.

LOGIT_MEDIAN_TOL = 0.03 sd and LOGIT_RMS_TOL = 0.075 sd, where logits can be
read (the CPU tests: `forward`, and chunked prefill then decode through the
paged pool): over the positions kept, the MEDIAN of the per-position RMS
logit error and the RMS over all of them. Both must hold. Readings (on the CPU;
`tiny-zaya` widened to hidden 256, 8 query and 2 KV heads of 32, 16
experts of 256 top-1 beside the skip, a router of 64, 4 layers, vocabulary
8,192; 8 rows x 128 positions, seeds 1-3; ~5% of the positions are near-ties
and left out), median / RMS: the bf16 program 0.009-0.017 / 0.039-0.053;
weights rounded to e4m3 0.140-0.157 / 0.181-0.196; without the skip
0.044-0.081 / 0.091-0.135; without EDA 0.247-0.273 / 0.275-0.302; without the
value shift 0.545-0.596 / 0.575-0.615; without the convolutions 0.642-0.648 /
0.652-0.661. 0.03 is 1.8 times the worst bf16 median and 0.68 of the best
median without the skip (the closest fault); 0.075 is 1.4 times the worst
bf16 RMS and 0.82 of the best RMS without the skip.

LOGIT_TOL = 0.1 sd, PASS_SHARE = 0.98, MIN_CHECKED = 8, where only tokens can
be read (the engine's probe): the SHARE RULE of `reference/laguna.py` with
this model's numbers, over ONE TEACHER-FORCED PASS. The harness hands
`check_tokens` the served tokens only, so `greedy_path` (run before the
server takes the memory) keeps the weights and the prompts, and
`check_tokens` feeds the reference each prompt with the served tokens
(`teacher_forced`): EVERY served position is scored against the reference
fed the same tokens before it, wherever the engine left the reference's own
greedy path. A served token is within tolerance when its reference logit is
within LOGIT_TOL sd (of that position's logits) of the maximum there; at
least PASS_SHARE of the positions must be, and at least MIN_CHECKED must have
been checked. The rule is sized with the router's flips IN (top-1: a flip is
a whole expert): 16-23% of the probed positions are router near-ties at the
cell's 20 layers. Readings ON THE CHIP at the cell's widths (TPU v5e, one
chip; share WITHIN 0.1 sd, and positions outside 0.02 / 0.05 / 0.1 sd):
- the bf16 ENGINE through the cell's server, probe 20 rows x 2,048 tokens + 896
  served (17,920 positions), seeds 3000000411-416: 0.9962 (219 / 126 / 68),
  0.9983 (156 / 97 / 30), 0.9965 (243 / 144 / 63), 0.9983 (159 / 64 / 31),
  0.9984 (158 / 78 / 29), 0.9968 (222 / 109 / 57);
- the faults, each copy's greedy token at every position of a fixed path (10
  probe prompts of 2,048 tokens, each followed by 896 tokens of the same
  text; 8,960 positions; seed 3000000411) scored by the reference: the
  reference itself 1.000 (0 / 0 / 0); WITHOUT THE SKIP 0.954 (1,225 / 844 /
  412); on e4m3 weights (rounded in arithmetic: a cast there and back may be
  elided by the compiler) 0.833 (2,433 / 2,085 / 1,497); without EDA 0.441;
  without the value shift 0.557; without the convolutions 0.188.
PASS_SHARE 0.98 lets 2.0% of the positions fall outside: 5.3 times the
engine's worst (0.38%) and 2.3 times under the skip's absence (4.6%), the
closest fault. (A probe of 16 new tokens a row checked only until a row's
first token off the reference's own path, ~300 positions: the skip's absence
read 0.992 of 121 there, like the engine, and the rule could not tell it.)
On the CPU at the widening above (32 rows x 8 / 32 new tokens, seed 1): bf16
1.000 / 0.998, e4m3 0.934 / 0.931, without EDA 0.875 / 0.877, the value
shift 0.664 / 0.720, the convolutions 0.531 / 0.624, the skip 0.992 / 0.983:
at 4 layers and a router of 64 the skip's absence moves too few tokens for
the rule, and the logit tolerances catch it there
(`benchmarks/tests/test_reference_zaya.py`). `check_tokens` also reports how
many positions lie outside each tolerance of GAP_LADDER, so the next
calibration needs no new run.
"""

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# The readings behind each are in the module's note.
LOGIT_MEDIAN_TOL = 0.03
LOGIT_RMS_TOL = 0.075
LOGIT_TOL = 0.1
PASS_SHARE = 0.98
ROUTER_TIE_MARGIN = 0.002
MIN_CHECKED = 8
LOSS_TOL = 0.02
AUX_TOL = 0.02
GAP_LADDER = (0.02, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3)
ROW_BLOCK = 256  # query rows whose scores are live at once
ROW_GROUP = 2    # sequences that go through the layers together (the
                 # probe's check runs beside the server's pool)
WITHOUT = ("conv", "value_shift", "eda", "skip")

F32 = jnp.float32
HYBRID = "hybrid"
SMALL = ("wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "cca_conv0",
         "cca_conv1", "cca_temp", "router_down", "router_gamma", "router_norm",
         "router_w1", "router_w2", "router", "res_attn", "res_mlp")


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _delay(a, j):
    """a (B, S, ...) moved j tokens later along S, zeros in front."""
    if j == 0:
        return a
    pad = [(0, 0), (j, 0)] + [(0, 0)] * (a.ndim - 2)
    return jnp.pad(a, pad)[:, : a.shape[1]]


def _unit(a):
    return a / jnp.maximum(jnp.linalg.norm(a, axis=-1, keepdims=True), 1e-6)


def _rope(x, rot, theta):
    """x: (B, S, H, d), positions 0..S-1: the first `rot` values of a head
    rotated (rotate-half pairing inside them); the rest as they are."""
    inv_freq = [theta ** (-2.0 * i / rot) for i in range(rot // 2)]
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1)


def _cca(c: Dict[str, Any], without, x, p):
    """1. of the note -> x'."""
    b, s, _ = x.shape
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    half, groups, per = hd // 2, h + kv, h // kv
    n = _rms_norm(x, p["attn_norm"], c["norm_eps"])
    q_t = (n @ p["wq"]).reshape(b, s, h, hd)
    k_t = (n @ p["wk"]).reshape(b, s, kv, hd)
    v = (n @ p["wv"]).reshape(b, s, kv, hd)
    if "value_shift" not in without:
        v = jnp.concatenate([v[..., :half], _delay(v[..., half:], 1)], axis=-1)
    u = jnp.concatenate([q_t, k_t], axis=2)                      # (b, s, 10, hd)
    if "conv" in without:
        c2 = u
    else:
        a = p["cca_conv0"].reshape(c["cca_time0"], groups, hd)
        c1 = sum(a[j] * _delay(u, j) for j in range(c["cca_time0"]))
        c2 = sum(jnp.einsum("bsgi,gij->bsgj", _delay(c1, j), p["cca_conv1"][j])
                 for j in range(c["cca_time1"]))
    group_of = jnp.arange(h) // per
    q = c2[:, :, :h] + (q_t + k_t[:, :, group_of]) / 2
    q_mean = jnp.mean(q_t.reshape(b, s, kv, per, hd), axis=3)
    k = c2[:, :, h:] + (q_mean + k_t) / 2
    q = _unit(q) * (p["cca_temp"] * math.sqrt(hd))[:, None]
    k = _unit(k)
    rot = int(hd * c["partial_rotary_factor"]) // 2 * 2
    q, k = _rope(q, rot, c["rope_theta"]), _rope(k, rot, c["rope_theta"])
    q = q.reshape(b, s, kv, per, hd)
    key_pos = jnp.arange(s)
    rows = []
    # A block of query rows at a time: the (H, S, S) float32 scores of a
    # long prompt would not fit beside the weights.
    for r0 in range(0, s, ROW_BLOCK):
        qb = q[:, r0:r0 + ROW_BLOCK]
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * hd ** -0.5
        seen = (r0 + jnp.arange(qb.shape[1]))[:, None] >= key_pos[None, :]
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        rows.append(jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v))
    attn = jnp.concatenate(rows, axis=1).reshape(b, s, h * hd) @ p["wo"]
    return p["res_attn"][0] * x + p["res_attn"][1] * attn


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(c: Dict[str, Any], without, x, depth, p):
    """2. of the note -> (x'', the router's carry, the router margin (B, S):
    the best of the 17 probabilities less the second best)."""
    n_experts = c["n_experts"]
    xn = _rms_norm(x, p["mlp_norm"], c["norm_eps"])
    gamma = 0.0 if "eda" in without else p["router_gamma"]
    depth = xn @ p["router_down"] + gamma * depth
    z = _rms_norm(depth, p["router_norm"], c["norm_eps"])
    z = jax.nn.gelu(z @ p["router_w1"], approximate=False)
    z = jax.nn.gelu(z @ p["router_w2"], approximate=False)
    z = z @ p["router"]                                           # (B, S, 17)
    if "skip" in without:
        z = z.at[..., n_experts].set(-jnp.inf)
    probs = jax.nn.softmax(z, axis=-1)
    ranked = jax.lax.top_k(probs, 2)[0]
    choice = jnp.argmax(z, axis=-1)          # the selection bias b is zero
    weight = jax.nn.one_hot(choice, n_experts + 1, dtype=F32) * probs

    def one_expert(out, e):
        gate, up, down, w = e
        y = _swiglu(xn, gate.astype(F32), up.astype(F32), down.astype(F32))
        return out + w[..., None] * y, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p["we_gate"], p["we_up"], p["we_down"],
         jnp.moveaxis(weight[..., :n_experts], -1, 0)))
    return (p["res_mlp"][0] * x + p["res_mlp"][1] * y, depth,
            ranked[..., 0] - ranked[..., 1], choice)


def _layer(c_items, without, x, depth, stack, index):
    """One block on `x` with layer `index` of the stack. The slice is taken
    inside the jitted function, next to the cast to float32, so no second
    copy of a layer's bf16 weights is ever held."""
    c = dict(c_items)
    p = jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False), stack)
    small = {k: p[k].astype(F32) for k in SMALL}
    p = {**p, **small}
    return _experts(c, without, _cca(c, without, x, p), depth, p)


_layer_jit = jax.jit(_layer, static_argnums=(0, 1))


def _sizes(config) -> Dict[str, Any]:
    """The sizes the reference reads, from a ModelConfig or from the fields
    a cell resolves (`cellfiles.resolve_model`): hashable, for the jit."""
    f = config if isinstance(config, dict) else vars(config)
    c = {k: f[k] for k in ("d_model", "n_heads", "n_kv_heads", "n_layers",
                           "norm_eps", "n_experts", "cca_time0", "cca_time1")}
    c["head_dim"] = f.get("head_size") or f["d_model"] // f["n_heads"]
    group = dict(f.get("rope_parameters") or ()).get(HYBRID)
    group = dict(group if isinstance(group, dict) else vars(group))
    c["rope_theta"] = float(group.get("rope_theta", group.get("theta")))
    c["partial_rotary_factor"] = float(group.get("partial_rotary_factor", 1.0))
    return c


def hidden(config, params, tokens, without=()):
    """tokens (B, S) -> (final-norm hidden states (B, S, D) float32, router
    statistics: the smallest router margin over the layers at each position
    (B, S), the choices (B, S, L) and the load-balance term, 0). ROW_GROUP
    sequences go through the layers at a time, so a probe of many prompts
    needs the memory of a few. `without`: the note's faults."""
    c = _sizes(config)
    without = tuple(sorted(without))
    assert set(without) <= set(WITHOUT), without
    c_items = tuple(sorted(c.items()))
    xs, margins, choices = [], [], []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, tokens.shape[0], ROW_GROUP):
            rows = tokens[r0:r0 + ROW_GROUP]
            x = jnp.take(params["embed"], rows, axis=0).astype(F32)
            depth = jnp.zeros(rows.shape + (params["layers"]["router_down"].shape[-1],), F32)
            margin, chosen = jnp.full(rows.shape, jnp.inf, F32), []
            for layer in range(c["n_layers"]):
                x, depth, m, choice = _layer_jit(
                    c_items, without, x, depth, params["layers"], layer)
                margin = jnp.minimum(margin, m)
                chosen.append(choice)
            xs.append(_rms_norm(x, params["final_norm"].astype(F32), c["norm_eps"]))
            margins.append(margin)
            choices.append(jnp.stack(chosen, axis=-1))
    return jnp.concatenate(xs), {"margin": jnp.concatenate(margins),
                                 "choices": jnp.concatenate(choices),
                                 "aux": jnp.zeros((), F32)}


def _head(params):
    return params["embed"].astype(F32).T


def logits(config, params, tokens, without=()):
    """tokens (B, S) -> logits (B, S, V) float32."""
    x, _ = hidden(config, params, tokens, without)
    with jax.default_matmul_precision("highest"):
        return x @ _head(params)


def _head_stats(x, embed, picks):
    """x (B, K, D) final-norm hidden states, embed (V, D), picks (B, K) ->
    per position the greatest logit, the logits' sd, the logit of `picks`
    and the greedy token. The vocabulary goes through a slice at a time:
    the (B, K, V) float32 logits of a long probe would not fit beside the
    server's pool."""
    vocab = embed.shape[0]
    n = next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)
    width = vocab // n

    def one_slice(carry, i):
        best, arg, total, squares, at = carry
        lg = x @ jax.lax.dynamic_slice_in_dim(embed, i * width, width).astype(F32).T
        top = jnp.max(lg, axis=-1)
        arg = jnp.where(top > best, jnp.argmax(lg, axis=-1) + i * width, arg)
        local = picks - i * width
        inside = (local >= 0) & (local < width)
        hit = jnp.take_along_axis(lg, jnp.clip(local, 0, width - 1)[..., None], axis=-1)
        return (jnp.maximum(best, top), arg, total + jnp.sum(lg, axis=-1),
                squares + jnp.sum(lg * lg, axis=-1),
                jnp.where(inside, hit[..., 0], at)), None

    zeros = jnp.zeros(picks.shape, F32)
    (best, arg, total, squares, at), _ = jax.lax.scan(
        one_slice, (zeros - jnp.inf, jnp.zeros(picks.shape, jnp.int32), zeros, zeros, zeros),
        jnp.arange(n))
    mean = total / vocab
    return best, jnp.sqrt(squares / vocab - mean * mean), at, arg


_head_stats_jit = jax.jit(_head_stats)


def teacher_forced(config, params, prompts, tokens, picks=None, without=()):
    """prompts (B, P), tokens (B, K): the K tokens a system chose after each
    prompt. The reference is fed the prompt and every token before the one
    it predicts (teacher forcing), so one pass scores a whole served path,
    wherever it left the reference's own greedy path. -> per predicted
    position (B, K): "picks", the reference's greedy token there (`without`:
    the faulty copy's); "gaps", how far the logit of `picks` (default:
    `tokens` themselves) lies under the greatest, in sd of that position's
    logits; "margin", the smallest router margin over the layers."""
    prompts, tokens = jnp.asarray(prompts, jnp.int32), jnp.asarray(tokens, jnp.int32)
    picks = tokens if picks is None else jnp.asarray(picks, jnp.int32)
    plen = prompts.shape[1]
    path = jnp.concatenate([prompts, tokens[:, :-1]], axis=1)
    out = {"picks": [], "gaps": [], "margin": []}
    for r0 in range(0, path.shape[0], ROW_GROUP):
        x, stats = hidden(config, params, path[r0:r0 + ROW_GROUP], without)
        with jax.default_matmul_precision("highest"):
            best, sd, at, arg = _head_stats_jit(
                x[:, plen - 1:], params["embed"], picks[r0:r0 + ROW_GROUP])
        out["picks"].append(arg)
        out["gaps"].append((best - at) / sd)
        out["margin"].append(stats["margin"][:, plen - 1:])
    return {k: jnp.concatenate(v) for k, v in out.items()}


# What `greedy_path` leaves for `check_tokens` (the probe's two halves).
_PROBE: Dict[str, Any] = {}


def greedy_path(config, params, prompts, steps: int):
    """The probe's first half, run before the server takes the memory:
    prompts (B, P) -> the reference's greedy token after each prompt (B, 1),
    its greatest logit and the logits' sd there (B, 1, 2), and its router
    margin (B, 1). `config`, `params` (the tree the server is handed, so
    keeping it costs no memory) and `prompts` stay for `check_tokens`,
    which holds all `steps` served tokens of each row to ONE teacher-forced
    pass (`teacher_forced`): the harness hands it only the served tokens,
    and a greedy path of `steps` tokens would cost `steps` passes."""
    del steps  # check_tokens reads however many the server returned
    _PROBE.update(config=config, params=params, prompts=prompts)
    x, stats = hidden(config, params, prompts)
    with jax.default_matmul_precision("highest"):
        best, sd, _, tok = _head_stats_jit(
            x[:, -1:], params["embed"], jnp.zeros((prompts.shape[0], 1), jnp.int32))
    return tok, jnp.stack([best, sd], axis=-1), stats["margin"][:, -1:]


def loss(config, params, batch, sample_rows: int = 0, sample_tail: int = 0):
    """The train step's objective on `batch` ({"inputs", "targets"} (B, S)):
    -> (cross-entropy mean, load-balance term (0 here), sample), as
    `reference/laguna.py`'s. One row goes through at a time."""
    inputs, targets = batch["inputs"], batch["targets"]
    ce_sum = jnp.zeros((), F32)
    sample = {"logits": [], "margin": []}
    for r in range(inputs.shape[0]):
        x, stats = hidden(config, params, inputs[r:r + 1])
        with jax.default_matmul_precision("highest"):
            lg = x @ _head(params)
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


def share_rule(gaps, margins) -> Dict[str, Any]:
    """The SHARE RULE (the module's note) over gaps (in sd) of the served
    tokens at the positions checked, with their router margins."""
    import numpy as np

    gaps = np.asarray(gaps, np.float32).ravel()
    margins = np.asarray(margins, np.float32).ravel()
    checked, passed = int(gaps.size), int(np.sum(gaps <= LOGIT_TOL))
    share = passed / checked if checked else 0.0
    return {
        "checked": checked, "passed": passed, "pass_share": share,
        "required_share": PASS_SHARE,
        "router_near_ties": int(np.sum(margins < ROUTER_TIE_MARGIN)),
        "worst_gap_sd": float(gaps.max(initial=0.0)), "tolerance_sd": LOGIT_TOL,
        "outside_at_sd": {str(t): int(np.sum(gaps > t)) for t in GAP_LADDER},
        "ok": checked >= MIN_CHECKED and share >= PASS_SHARE,
    }


def check_tokens(server_tokens, ref_tokens, ref_logits, ref_margins) -> Dict[str, Any]:
    """The probe's second half: the server's greedy tokens held to one
    teacher-forced pass of the reference over prompt + served tokens (what
    `greedy_path` kept), by the share rule at EVERY served position.
    `ref_tokens` (the reference's first greedy token a row) is only
    reported against the server's first token; `ref_logits` and
    `ref_margins` are its statistics there, which the pass recomputes."""
    import numpy as np

    del ref_logits, ref_margins
    steps = min((len(row) for row in server_tokens), default=0)
    if steps == 0:
        return {**share_rule([], []), "first_tokens_greedy": 0}
    served = np.asarray([list(row)[:steps] for row in server_tokens], np.int32)
    scored = jax.device_get(teacher_forced(
        _PROBE["config"], _PROBE["params"], _PROBE["prompts"], served))
    result = share_rule(scored["gaps"], scored["margin"])
    result["first_tokens_greedy"] = int(np.sum(
        served[:, 0] == np.asarray(ref_tokens).reshape(len(served), -1)[:, 0]))
    return result
