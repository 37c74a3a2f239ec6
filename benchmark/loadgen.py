"""The one general generator of traffic.  A traffic mix is a data file
(``traffic/<name>.json``); everything here is driven by its parameters and
``--seed``.

Steadiness by construction: every size (lengths, gaps, turns) is drawn as a
seeded PERMUTATION of one fixed multiset — the distribution's evenly spaced
quantiles — so that every seed gives the same set of sizes and arrivals, in
another order, and the same total work.  Only the token ids and the order
change with the seed.  (The arithmetic of ``serve/replay.py``'s Poisson
arrivals, with the draws replaced by quantiles.)
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Iterator, List, Tuple

N_SPECIAL = 5   # [PAD] [UNK] [CLS] [SEP] [MASK]: never drawn as content


def quantiles(spec: dict, n: int) -> List[float]:
    """``n`` evenly spaced quantiles of the distribution ``spec`` names."""
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "const":
        return [spec["value"]] * n
    if dist == "uniform":
        return [spec["lo"] + q * (spec["hi"] - spec["lo"]) for q in qs]
    if dist == "exponential":
        return [-math.log(1.0 - q) * spec["mean"] for q in qs]
    if dist == "lognormal":
        nd = NormalDist()
        return [min(spec["hi"], max(spec["lo"], spec["median"] * math.exp(
            spec["sigma"] * nd.inv_cdf(q)))) for q in qs]
    if dist == "choice":
        # values with weights -> counts that sum to n (largest remainders)
        vals, wts = spec["values"], spec["weights"]
        tot = float(sum(wts))
        exact = [w / tot * n for w in wts]
        counts = [int(x) for x in exact]
        order = sorted(range(len(vals)), key=lambda i: exact[i] - counts[i],
                       reverse=True)
        for i in order[: n - sum(counts)]:
            counts[i] += 1
        return [v for v, c in zip(vals, counts) for _ in range(c)]
    raise ValueError(f"unknown distribution {dist!r}")


def permuted(spec: dict, n: int, rng: random.Random, integer: bool = True):
    xs = quantiles(spec, n)
    if integer:
        xs = [int(round(x)) for x in xs]
    rng.shuffle(xs)
    return xs


def token_ids(rng: random.Random, n: int, vocab_size: int) -> List[int]:
    return [rng.randrange(N_SPECIAL, vocab_size) for _ in range(n)]


# ------------------------------------------------------------ train_epochs

def alphabet(vocab_size: int) -> List[str]:
    """The vocabulary's content characters: CJK ideographs, one token each."""
    chars = [chr(c) for c in range(0x4E00, 0x9FA6)] \
        + [chr(c) for c in range(0x3400, 0x4DB6)]
    return chars[: vocab_size - N_SPECIAL]


def vocab_lines(vocab_size: int) -> List[str]:
    return ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + alphabet(vocab_size)


def corpus(traffic: dict, seed: int, vocab_size: int, n_labels: int
           ) -> List[Tuple[str, int]]:
    """``rows`` (text, label) pairs: text lengths are the permuted multiset
    of ``traffic['text_chars']``; the label is a function of the text."""
    rng = random.Random(seed)
    chars = alphabet(vocab_size)
    lengths = permuted(traffic["text_chars"], traffic["rows"], rng)
    out = []
    for n in lengths:
        text = "".join(rng.choice(chars) for _ in range(n))
        out.append((text, sum(map(ord, text)) % n_labels))
    return out


# ------------------------------------------------------ open_loop_sessions

class Request:
    __slots__ = ("due", "prompt", "max_new", "session", "turn")

    def __init__(self, due, prompt, max_new, session, turn):
        self.due, self.prompt, self.max_new = due, prompt, max_new
        self.session, self.turn = session, turn


def open_loop_sessions(traffic: dict, seed: int, vocab_size: int,
                       horizon_s: float, max_len: int) -> List[Request]:
    """Requests due in ``[0, horizon_s)``, sorted by due time.

    Sessions start as a Poisson process of ``session_rate_per_s`` (gaps: the
    permuted exponential multiset, scaled to fill the horizon).  A session
    has one of the system prompts (Zipf) and 1..3 turns; turn k's prompt is
    the system prompt, the earlier turns' user texts and answers, and its own
    user text; it asks for as many new tokens as its answer is long.  A turn
    is due one nominal answer time plus a think time after the one before; a
    turn that would pass ``max_len`` positions ends its session.

    The sessions' SHAPES (turns, lengths, think times, which system prompt)
    come from ``shape_seed`` in the traffic file, not from ``seed``: every
    seed sends the same multiset of requests.  The seed orders the sessions'
    starts and draws the token ids.  The schedule is periodic in the horizon
    (a turn due after the horizon's end is due that much after its start), so
    every turn of every session is sent exactly once in every run and at time
    0 sessions are already in every stage of their life."""
    shape = random.Random(traffic.get("shape_seed", 0))
    rng = random.Random(seed)
    rate = traffic["session_rate_per_s"]
    n = max(1, int(round(rate * horizon_s)))
    sys_spec = traffic["system_prompts"]
    zipf = {"dist": "choice", "values": list(range(sys_spec["count"])),
            "weights": [1.0 / (k + 1) ** sys_spec["zipf_s"]
                        for k in range(sys_spec["count"])]}
    which = permuted(zipf, n, shape)
    turns = permuted(traffic["turns"], n, shape)
    n_turns = sum(turns)
    users = permuted(traffic["user_tokens"], n_turns, shape)
    answers = permuted(traffic["answer_tokens"], n_turns, shape)
    thinks = permuted(traffic["think_s"], n_turns, shape, integer=False)
    gaps = permuted({"dist": "exponential", "mean": 1.0}, n, rng,
                    integer=False)
    scale = horizon_s / sum(gaps)
    order = list(range(n))
    rng.shuffle(order)           # which session takes which start
    systems = [token_ids(rng, sys_spec["tokens"], vocab_size)
               for _ in range(sys_spec["count"])]
    first = [0] * n              # index of session s's first turn
    for s in range(1, n):
        first[s] = first[s - 1] + turns[s - 1]
    out: List[Request] = []
    t = 0.0
    for slot, s in enumerate(order):
        t += gaps[slot] * scale
        due = t
        history = list(systems[which[s]])
        for k in range(turns[s]):
            j = first[s] + k
            prompt = history + token_ids(rng, users[j], vocab_size)
            if len(prompt) + answers[j] > max_len:
                break
            out.append(Request(due % horizon_s, prompt, answers[j], s, k))
            history = prompt + token_ids(rng, answers[j], vocab_size)
            due += answers[j] * traffic["nominal_itl_s"] + thinks[j]
    out.sort(key=lambda r: r.due)
    return out


# -------------------------------------------------- closed_loop_saturating

def closed_loop_prompts(traffic: dict, seed: int, vocab_size: int
                        ) -> Iterator[Tuple[List[int], int]]:
    """An endless stream of (prompt, max_new): prompt lengths cycle through
    the permuted multiset of ``traffic['prompt_tokens']``, reshuffled each
    cycle; no two prompts share a prefix (ids are drawn fresh).

    ``traffic['new_tokens']`` is a number, the same for every request, or a
    distribution as ``prompt_tokens`` is: then the answer lengths are a
    permuted multiset of their own, a cycle long, shuffled independently of
    the prompts' — streams seated together do not finish together."""
    rng = random.Random(seed)
    cycle, new = traffic["cycle"], traffic["new_tokens"]
    while True:
        lengths = permuted(traffic["prompt_tokens"], cycle, rng)
        news = permuted(new, cycle, rng) if isinstance(new, dict) \
            else [new] * cycle
        for n, k in zip(lengths, news):
            yield token_ids(rng, n, vocab_size), k
