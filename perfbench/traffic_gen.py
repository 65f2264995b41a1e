"""The one general traffic generator: every traffic file under
``perfbench/traffic/`` is parameters for the functions here.

Two rules keep runs comparable.  The *shape* of the work (how many requests,
their lengths, the gaps between them and their order, the size of every
batch) comes from ``shape_seed`` in the traffic file and is the same in every
run; ``--seed`` chooses every token value (and the weights).  So two seeds
never differ in the amount of work or in when it arrives.  A seed-drawn order
of the same requests was tried twice on the chip and could not be bounded
(PERF.md, PR 23): at the knee the order of long and short requests decides
the tail (a 95th percentile of 60 ms under one order, 390 ms under another),
and below it the 95th percentile of the token gap sits at the edge between
rounds with one admission and rounds with two, which some orders cross.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# ------------------------------------------------------------ training


def zipf_cdf(vocab: int, alpha: float) -> np.ndarray:
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** alpha
    return np.cumsum(weights / weights.sum())


def token_batches(batch_size: int, seq_len: int, vocab: int, seed: int,
                  alpha: float = 1.0):
    """Endless [batch, seq] int32 batches of Zipf-distributed token ids (a
    unigram distribution a model can learn, so the loss must fall), drawn
    on the host step by step: the input pipeline does real work."""
    cdf = zipf_cdf(vocab, alpha)
    rng = np.random.default_rng([int(seed), 0x70CE])
    while True:
        draws = rng.random((batch_size, seq_len))
        yield np.minimum(np.searchsorted(cdf, draws), vocab - 1).astype(
            np.int32)


# -------------------------------------------------------------- serving


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float            # offset from the opening of the schedule
    system: int             # which shared system prompt
    prompt: np.ndarray      # system prompt + user message, int32
    max_new: int


def _heavy_tail(rng, spec: dict, n: int) -> np.ndarray:
    """Log-normal lengths clipped to [min, max]: ``median`` and ``sigma``
    are those of the underlying normal in log space."""
    draws = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(draws), spec["min"], spec["max"]).astype(int)


def serve_shape(traffic: dict, seconds: float) -> dict:
    """The part of a serving schedule that ``--seed`` may not change:
    request count, inter-arrival gaps, user-message and output lengths, and
    which system prompt each request carries."""
    rng = np.random.default_rng([int(traffic["shape_seed"]), 1])
    rate = float(traffic["arrivals"]["rate_per_s"])
    if traffic["arrivals"]["process"] != "poisson":
        raise ValueError("arrivals.process: only 'poisson' is generated")
    n = max(1, int(round(rate * seconds)))
    # gaps of a Poisson process, rescaled so the last arrival lands just
    # inside the window in every run
    gaps = rng.exponential(1.0 / rate, n)
    gaps *= (seconds * (n - 0.5) / n) / gaps.sum()
    systems = traffic["sessions"]["system_prompts"]
    weights = np.asarray(traffic["sessions"]["popularity"], np.float64)
    return {
        "n": n, "gaps": gaps,
        "user_len": _heavy_tail(rng, traffic["user_tokens"], n),
        "out_len": _heavy_tail(rng, traffic["output_tokens"], n),
        "system": rng.choice(len(systems), n, p=weights / weights.sum()),
    }


def system_prompts(traffic: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """The shared system prompts: lengths from the traffic file, tokens
    from ``--seed``, first tokens all different so that no two share a
    tree edge."""
    rng = np.random.default_rng([int(seed), 2])
    lengths = traffic["sessions"]["system_prompts"]
    firsts = rng.choice(vocab, len(lengths), replace=False)
    out = []
    for first, length in zip(firsts, lengths):
        body = rng.integers(0, vocab, length).astype(np.int32)
        body[0] = first
        out.append(body)
    return out


def serve_schedule(traffic: dict, vocab: int, seed: int, seconds: float,
                   systems: list[np.ndarray]) -> list[Request]:
    """The measured requests: the fixed shape with token values from the
    seed.  A user message starts with a token unique to its request, so a
    prompt never matches the prefix tree deeper than its system prompt and
    the set of compiled shapes is the same in every run."""
    shape = serve_shape(traffic, seconds)
    rng = np.random.default_rng([int(seed), 3])
    due = np.cumsum(shape["gaps"])
    unique = rng.choice(vocab, shape["n"], replace=False)
    requests = []
    for i in range(shape["n"]):
        user = rng.integers(0, vocab, shape["user_len"][i]).astype(np.int32)
        user[0] = unique[i]
        system = int(shape["system"][i])
        requests.append(Request(
            index=i, due_s=float(due[i]), system=system,
            prompt=np.concatenate([systems[system], user]),
            max_new=int(shape["out_len"][i])))
    return requests


def warmup_requests(traffic: dict, vocab: int, seed: int,
                    systems: list[np.ndarray]) -> list[Request]:
    """One request per (system-prompt bucket, user-message bucket) the
    traffic can produce, so that every program the window needs is built
    during set-up.  Buckets are the server's powers of two from 16."""
    rng = np.random.default_rng([int(seed), 4])
    spec = traffic["user_tokens"]
    buckets = sorted({_bucket(n) for n in range(spec["min"],
                                                spec["max"] + 1)})
    requests = []
    seen_system_buckets = set()
    for s, sys_tokens in enumerate(systems):
        if _bucket(len(sys_tokens)) in seen_system_buckets:
            continue
        seen_system_buckets.add(_bucket(len(sys_tokens)))
        for b in buckets:
            length = min(b, spec["max"])
            user = rng.integers(0, vocab, length).astype(np.int32)
            requests.append(Request(
                index=-1, due_s=0.0, system=s,
                prompt=np.concatenate([sys_tokens, user]),
                max_new=int(traffic["warmup"]["max_new"])))
    return requests


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def describe_lengths(values) -> dict:
    values = np.asarray(values)
    return {"n": int(values.size), "min": int(values.min()),
            "p50": float(np.percentile(values, 50)),
            "p95": float(np.percentile(values, 95)),
            "max": int(values.max()), "mean": float(values.mean())}
