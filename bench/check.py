"""Whether what the timed path served is right.

Once the window has closed and the server is freed, a sample of the
requests that streamed tokens, finished or still in flight, is run
through the configuration's plain reference over prompt + streamed
tokens. The sample always holds the request with the deepest context
(prompt + streamed) and the one with the most streamed tokens; the rest
are drawn from the seed. For each streamed token the gap by which the
reference puts its logit below the reference's best logit at that
position is read; the widest gap over the sample is compared with the
cell's limit (``bench/limits/<cell>.json``). Greedy decoding serves the
top token of its own (bfloat16, int8-KV) logits, so a right server keeps
every gap near zero, and a wrong token or a corrupt cache opens it wide.

Counts are held to 0 beside it: attempted requests that never got a
first token, a run that streamed no token to compare, finished requests
whose output is not as long as asked (EOS is off, so every request runs
to its length), and finished requests whose collected output is not the
tokens they streamed.
"""
from __future__ import annotations

import numpy as np

from bench import plugins

SAMPLE = 4          # requests compared in every run


def sample(sent: list, seed: int) -> list:
    """Of the requests that streamed a token: the one with the deepest
    context, the one with the most tokens, and SAMPLE - 2 or more drawn
    from the seed, SAMPLE in all (fewer where fewer streamed)."""
    live = [s for s in sent if s.served]
    if not live:
        return []
    deepest = max(range(len(live)), key=lambda i: (
        len(live[i].req.tokens) + len(live[i].served), len(live[i].served)))
    longest = max(range(len(live)), key=lambda i: (
        len(live[i].served), len(live[i].req.tokens)))
    first = sorted({deepest, longest})
    rest = [i for i in range(len(live)) if i not in first]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), min(SAMPLE - len(first), len(rest)),
                      replace=False)
    return [live[i] for i in first] + [live[rest[i]] for i in sorted(pick)]


def compared(rec, seed: int) -> list:
    """The (prompt, streamed tokens) pairs of one run's sample."""
    return [(s.req.tokens, np.asarray(s.served, np.int32))
            for s in sample(list(rec.sent.values()), seed)]


def gaps(w: dict, cfg: dict, seqs: list, control: bool = False) -> list:
    """Per sequence (prompt, served): the reference's gap at each served
    token, and, with ``control``, the gap of the token that the control's
    lower precision puts first at each of those positions."""
    ref = plugins.reference(cfg)
    out = []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served]).astype(np.int32)
        rows = np.arange(len(prompt) - 1, len(toks) - 1)
        cands = [served]
        if control:
            _, _, top = ref.score(w, cfg, toks, rows, np.zeros((0, len(rows)),
                                                           np.int32),
                                  control=True)
            cands.append(top)
        best, picked, _ = ref.score(w, cfg, toks, rows, np.stack(cands))
        out.append(best[None] - picked)
    return out


def counts(rec) -> dict:
    """The counts held to 0, each as {"value", "limit"}."""
    att = [s for s in rec.sent.values() if s.in_window]
    done = [s for s in rec.sent.values() if s.output is not None]
    return {
        "unanswered": {"value": sum(s.t_first is None for s in att),
                       "limit": 0},
        "none_served": {"value": int(not any(s.served for s in
                                             rec.sent.values())),
                        "limit": 0},
        "short_outputs": {"value": sum(len(s.output) != s.req.max_new
                                       for s in done), "limit": 0},
        "stream_mismatch": {"value": sum(
            not np.array_equal(s.output, s.served) for s in done),
            "limit": 0},
    }


def checks(rec, w: dict, cfg: dict, seed: int, limit: dict):
    """The numbers compared, each as {"value", "limit"}, and how many
    served tokens the reference read."""
    g = gaps(w, cfg, compared(rec, seed))
    widest = max((float(x[0].max()) for x in g), default=0.0)
    return dict(counts(rec), max_logit_gap={
        "value": widest, "limit": limit["max_logit_gap"]}), \
        int(sum(len(x[0]) for x in g))


def passed(c: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in c.values())
