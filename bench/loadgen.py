"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) is either

* ``"loop": "open"``: requests due on a schedule at ``rate_per_s``, with
  ``arrivals`` ``"poisson"`` (exponential gaps); or
* ``"loop": "closed"``: ``clients`` callers, each sending its next request
  when the last one finished.

``prompt`` and ``output`` give lognormal lengths (``median``, ``sigma``,
clipped to ``min``..``max``). ``prefixes`` (optional) puts one of
``count`` shared system prompts of ``tokens`` tokens, picked by Zipf
popularity with exponent ``zipf_s``, in front of every prompt and names it
as the request's ``prefix_id``.

``ramp_s`` of the same traffic runs before the window opens.

Every seed gets the same work. Lengths, gaps and prefix picks are drawn
by stratified quantiles in blocks (of the client count, or of the
requests due in the ramp or in one window). Within a block the quantiles
are paired into requests (prompt, answer, prefix) by a permutation that
depends on the block's size alone. ``order`` says who orders them:

* ``"seed"``: the seed shuffles the order of the requests and, apart, of
  the gaps; two seeds ask for the same requests in another order;
* ``"fixed"``: two more permutations fixed by the block's size order the
  requests and the gaps, so every seed replays one schedule. An open
  loop's tail over a window of tens of requests moves with the order
  alone (see PERF.md), so a mix judged on that tail takes this.

Token ids, the system prompts' among them, are drawn from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    tokens: np.ndarray            # prompt, int32
    max_new: int
    prefix_id: str | None
    due_s: float                  # open loop: due time from window start


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified lognormal lengths, clipped (sorted ascending)."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _gap_quantiles(mix: dict, n: int) -> np.ndarray:
    """n stratified exponential inter-arrival gaps, mean 1 / rate."""
    kind = mix.get("arrivals", "poisson")
    if kind != "poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g / g.mean() / mix["rate_per_s"]


def _zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of n requests pick each prefix rank (largest remainder)."""
    p = 1.0 / np.arange(1, count + 1) ** s
    p /= p.sum()
    raw = p * n
    out = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - out))[: n - out.sum()]:
        out[i] += 1
    return out


def generate(mix: dict, vocab: int, seed: int, seconds: float):
    """Yield the requests of one run, without end, in blocks.

    Open loop: a block of ``ramp_s`` due in ``[-ramp_s, 0)``, then blocks
    of ``seconds`` each, the first due in ``[0, seconds)``; each block's
    gaps sum to exactly its length. Closed loop: blocks of ``clients``
    requests, taken in order as callers free up."""
    if mix["order"] not in ("seed", "fixed"):
        raise ValueError(f"unknown order {mix['order']!r}")
    fixed = mix["order"] == "fixed"
    rng = np.random.default_rng(seed)
    pre = mix.get("prefixes")
    if pre:
        sys_prompts = [rng.integers(0, vocab, pre["tokens"], dtype=np.int32)
                       for _ in range(pre["count"])]
    if mix["loop"] == "closed":
        spans = [None]
    else:
        spans = [mix["ramp_s"], seconds] if mix["ramp_s"] else [seconds]
    t = -float(mix.get("ramp_s", 0.0)) if mix["loop"] == "open" else 0.0
    blocks: dict = {}
    while True:
        span = spans.pop(0) if len(spans) > 1 else spans[0]
        if span not in blocks:
            b = (int(mix["clients"]) if span is None
                 else max(1, round(mix["rate_per_s"] * span)))
            pair = np.random.default_rng(b)          # not the run's seed
            blocks[span] = (
                b, _lognormal_quantiles(mix["prompt"], b),
                pair.permutation(_lognormal_quantiles(mix["output"], b)),
                pair.permutation(np.repeat(
                    np.arange(pre["count"]),
                    _zipf_counts(pre["count"], pre["zipf_s"], b)))
                if pre else None,
                None if span is None else _gap_quantiles(mix, b),
                pair.permutation(b), pair.permutation(b))
        b, prompt_q, out_q, picks, gaps, lay, gap_lay = blocks[span]
        order = lay if fixed else rng.permutation(b)
        lens, outs = prompt_q[order], out_q[order]
        pick = picks[order] if pre else None
        if gaps is not None:
            gaps = gaps * span / gaps.sum()
            gap = gaps[gap_lay] if fixed else rng.permutation(gaps)
        else:
            gap = None
        for i in range(b):
            toks = rng.integers(0, vocab, int(lens[i]), dtype=np.int32)
            pid = None
            if pre:
                toks = np.concatenate([sys_prompts[pick[i]], toks])
                pid = f"sys{pick[i]}"
            yield Request(toks, int(outs[i]), pid, t)
            if gap is not None:
                t += float(gap[i])
