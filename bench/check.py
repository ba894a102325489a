"""Whether what the timed path served is correct.

After the window has closed and the program's state is freed, a sample of
the requests the engine finished, drawn from the seed and holding the
longest of them, is run through the plain reference of the configuration's
family (``bench/families/<family>/reference.py``) once per request: the
prompt followed by the served tokens. Every served token was a greedy
choice, so at its position the reference's own best logit should lie above
the served token's logit by no more than the rounding of the program's
arithmetic can explain. The numbers compared are the widest such gap over
every sampled token (in logits) and the mean gap; their limits, and the
readings each was set from, are in ``bench/limits/<cell>.json``.

A request that finished without all its tokens is wrong outright.
"""

from __future__ import annotations

import numpy as np

from bench import families


def precision_view(p: dict) -> dict:
    return {"act_bits": p["act_bits"], "act_clip": p["act_clip"],
            "kv_bits": p["kv_bits"], "weight_bits": dict(p["weight_bits"])}


def reference_scores(seed: int, c: dict, prec: dict, seq, targets, **lower):
    """The plain reference of ``c``'s family over ``seq`` under precision
    ``prec``: at every position the largest logit, the logit of
    ``targets`` and the argmax (``lower``: kv_bits / act_bits)."""
    R = families.get(c).reference
    return R.scores(seed, R.model_view(c), precision_view(prec), seq, targets, **lower)


def sample(requests: dict, seed: int, min_tokens: int, max_requests: int) -> list:
    """The finished requests to compare: the longest, then others in seed
    order until ``min_tokens`` served tokens or ``max_requests``."""
    done = sorted((r for r in requests.values() if r.status == "done"),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    pick, n = [longest], len(longest.out)
    for i in order:
        if n >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(rest[i])
        n += len(rest[i].out)
    return pick


def gaps(seed: int, c: dict, prec: dict, prompt, out, **lower) -> np.ndarray:
    """Per served token: reference best logit - reference logit of the
    token served (``lower``: kv_bits / act_bits of a lower precision)."""
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(out[:-1], np.int32)])
    targets = np.zeros(len(seq), np.int32)
    P = len(prompt)
    targets[P - 1:] = out
    mx, at, _ = reference_scores(seed, c, prec, seq, targets, **lower)
    return (mx - at)[P - 1:]


def numbers(gs: list) -> dict:
    """The numbers compared, over every compared token's gap (``gs``: one
    array per request): the widest gap and the mean gap."""
    g = np.concatenate(gs) if gs else np.zeros(0)
    if not g.size:
        return {"widest_logit_gap": 0.0, "mean_logit_gap": 0.0}
    return {"widest_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean())}


def judge(cell: dict, gs: list, n_short: int) -> dict:
    """``correct`` and every number beside its limit: each number the
    cell's limits file names at most its limit, at least ``min_tokens``
    tokens compared, no request short of its tokens."""
    lim, chk = cell["limits"], cell["mix"]["check"]
    vals = numbers(gs)
    out = {k: {"value": vals[k], "limit": lim[k]["limit"]} for k in lim}
    n = sum(len(g) for g in gs)
    out["tokens_compared"] = {"value": n, "limit": chk["min_tokens"]}
    out["short_requests"] = {"value": n_short, "limit": 0}
    ok = (all(v["limit"] is not None and v["value"] <= v["limit"]
              for k, v in out.items() if k in lim)
          and n >= chk["min_tokens"] and not n_short)
    return {"correct": bool(ok), "numbers": out}


def check(cell: dict, rec: dict, seed: int) -> dict:
    c, chk = cell["config"], cell["mix"]["check"]
    picked = sample(rec["requests"], seed, chk["min_tokens"], chk["max_requests"])
    short = [r.rid for r in rec["requests"].values()
             if r.status == "done" and len(r.out) != r.max_new]
    return judge(cell, [gaps(seed, c, c["precision"], r.prompt, r.out) for r in picked],
                 len(short))
