"""Prompts and lengths for the serving mixes: one general builder that the
open-loop and closed-loop generators share.

The native server's byte tokenizer pads or TRUNCATES every prompt from the
left to a power-of-two bucket (`Engine.encode` in
examples/deployment/native/server.py). A prompt whose encoded length is not
exactly a bucket loses its head, so a shared prefix stops being shared and
the length is not the one drawn. Prompts are therefore built so that the
chat template around the content encodes to exactly the drawn bucket, in
ASCII (one byte, one token). `encode` below is a copy of the server's rule,
kept here so the tests can hold the builder to it; PERF.md lists the
original under Open questions.
"""

import math
import random
from dataclasses import dataclass
from typing import Any, Dict, List

# The server renders one user message as f"user: {content}" + "\nassistant:".
TEMPLATE_HEAD = "user: "
TEMPLATE_TAIL = "\nassistant:"
TEMPLATE_TOKENS = len(TEMPLATE_HEAD) + len(TEMPLATE_TAIL)
MIN_BUCKET = 32
# Printable ASCII without the two characters JSON escapes.
ALPHABET = "".join(chr(c) for c in range(32, 127) if chr(c) not in '"\\')


@dataclass(frozen=True)
class Limits:
    """What the server's `encode` reads from its configuration."""
    vocab_size: int
    max_seq_len: int
    max_new_tokens: int

    @classmethod
    def of(cls, cell) -> "Limits":
        return cls(
            vocab_size=cell.model_fields["vocab_size"],
            max_seq_len=cell.model_fields["max_seq_len"],
            max_new_tokens=cell.server_arg("--max-new-tokens", 64),
        )


def render(content: str) -> str:
    return TEMPLATE_HEAD + content + TEMPLATE_TAIL


def encode(text: str, limits: Limits) -> List[int]:
    """Copy of `Engine.encode`: bytes, capped at the vocabulary, cut from the
    left to the prompt limit, then padded or cut from the left to the largest
    power of two that is not above the length."""
    ids = [min(b, limits.vocab_size - 1) for b in text.encode()] or [0]
    limit = limits.max_seq_len - limits.max_new_tokens
    ids = ids[-limit:] if limit > 0 else ids[:1]
    bucket = MIN_BUCKET
    while bucket * 2 <= len(ids):
        bucket *= 2
    bucket = min(bucket, limit if limit > 0 else bucket)
    if len(ids) < bucket:
        ids = [10] * (bucket - len(ids)) + ids
    else:
        ids = ids[-bucket:]
    return ids


def ascii_text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(ALPHABET, k=n))


def is_bucket(n_tokens: int, limits: Limits) -> bool:
    return (n_tokens >= MIN_BUCKET and n_tokens & (n_tokens - 1) == 0
            and n_tokens <= limits.max_seq_len - limits.max_new_tokens)


def content_for(total_tokens: int, head: str, rng: random.Random) -> str:
    """Content whose rendered prompt has exactly `total_tokens` tokens and
    starts with the shared `head` (itself drawn by `shared_head`)."""
    own = total_tokens - TEMPLATE_TOKENS - len(head)
    if own < 0:
        raise ValueError(f"{total_tokens} tokens cannot hold a head of {len(head)}")
    return head + ascii_text(rng, own)


def shared_head(head_tokens: int, rng: random.Random) -> str:
    """The part of the content that makes the first `head_tokens` tokens of
    the prompt the same for every request of a session."""
    return ascii_text(rng, head_tokens - len(TEMPLATE_HEAD)) if head_tokens else ""


def stratified(dist: Dict[str, Any], n: int, rng: random.Random) -> List[int]:
    """`n` draws from `dist` as a FIXED multiset in a seeded order: the i-th
    of n evenly spaced quantiles, shuffled. Every seed then offers the same
    work and only its order differs, so runs differ by arrivals and not by
    how many long requests the seed happened to draw."""
    kind = dist["dist"]
    if kind == "const":
        values = [int(dist["value"])] * n
    elif kind == "choice":
        # Largest remainders: counts proportional to the weights, summing to n.
        exact = [w * n / sum(dist["weights"]) for w in dist["weights"]]
        counts = [int(e) for e in exact]
        by_remainder = sorted(range(len(exact)), key=lambda i: exact[i] - counts[i],
                              reverse=True)
        for i in by_remainder[: n - sum(counts)]:
            counts[i] += 1
        values = [v for v, c in zip(dist["values"], counts) for _ in range(c)]
    elif kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        values = [lo + int((i + 0.5) / n * (hi - lo + 1)) for i in range(n)]
    elif kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        values = [
            int(round(min(dist["max"], max(dist["min"], math.exp(
                mu + sigma * _normal_quantile((i + 0.5) / n))))))
            for i in range(n)
        ]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    rng.shuffle(values)
    return values


def _normal_quantile(p: float) -> float:
    """Inverse standard normal CDF (Acklam's rational approximation, relative
    error 1e-9): lengths must not depend on a library's version."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00)
    if p < 0.02425:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0]*q + c[1])*q + c[2])*q + c[3])*q + c[4])*q + c[5]) / \
            ((((d[0]*q + d[1])*q + d[2])*q + d[3])*q + 1)
    if p > 1 - 0.02425:
        return -_normal_quantile(1 - p)
    q = p - 0.5
    r = q * q
    return (((((a[0]*r + a[1])*r + a[2])*r + a[3])*r + a[4])*r + a[5]) * q / \
        (((((b[0]*r + b[1])*r + b[2])*r + b[3])*r + b[4])*r + 1)


def scaled_for_rehearsal(mix: Dict[str, Any], rehearsal: Dict[str, Any]) -> Dict[str, Any]:
    """The mix at a size the CPU rehearsal can serve: lengths divided, floors
    and caps applied, the lead-in shortened. Shapes (sharing, arrivals) stay."""
    div, floor = rehearsal["length_divisor"], rehearsal["min_prompt_tokens"]
    cap = rehearsal["max_output_tokens"]

    def shrink_prompt(d):
        d = dict(d)
        for key in ("value", "min", "max", "median"):
            if key in d:
                d[key] = max(floor, d[key] // div)
        if "values" in d:
            d["values"] = [max(floor, v // div) for v in d["values"]]
        return d

    def cap_output(d):
        d = dict(d)
        for key in ("value", "min", "max", "median"):
            if key in d:
                d[key] = max(2, min(cap, d[key]))
        return d

    out = dict(mix)
    out["lead_in_s"] = rehearsal["lead_in_s"]
    out["prompt"] = {
        "shared_head_tokens": mix["prompt"]["shared_head_tokens"] // div,
        "total_tokens": shrink_prompt(mix["prompt"]["total_tokens"]),
    }
    out["output_tokens"] = cap_output(mix["output_tokens"])
    return out
