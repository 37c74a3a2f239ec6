"""The generators' schedules from a seed."""
import collections
import os

import pytest

from benchmark import common, loadgen

TRAFFIC = os.path.join(common.HERE, "traffic")


def load(name):
    return common.load_json(TRAFFIC, name + ".json")


def sessions(seed, horizon=45.0):
    return loadgen.open_loop_sessions(load("decode-chat-belowknee"), seed, 21128,
                                      horizon, 512)


def key(reqs):
    return [(round(r.due, 9), tuple(r.prompt), r.max_new) for r in reqs]


def test_same_seed_same_arrivals_and_lengths():
    assert key(sessions(7)) == key(sessions(7))
    assert key(sessions(7)) != key(sessions(8))


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 12345])
def test_every_seed_sends_the_same_multiset(seed):
    a, b = sessions(0), sessions(seed)
    shape = lambda rs: collections.Counter((len(r.prompt), r.max_new, r.turn) for r in rs)
    assert shape(a) == shape(b)
    assert len(a) == len(b) and all(0 <= r.due < 45.0 for r in b)
    assert all(len(r.prompt) + r.max_new <= 512 for r in b)


def test_later_turns_resend_the_history():
    reqs = sessions(3)
    by_session = collections.defaultdict(dict)
    for r in reqs:
        by_session[r.session][r.turn] = r
    later = [(s[0], s[1]) for s in by_session.values() if 0 in s and 1 in s]
    assert later, "no session with two turns"
    for first, second in later:
        assert second.prompt[: len(first.prompt)] == first.prompt
        assert len(second.prompt) >= len(first.prompt) + first.max_new


def test_system_prompts_are_shared_and_zipf():
    reqs = [r for r in sessions(5) if r.turn == 0]
    heads = collections.Counter(tuple(r.prompt[:192]) for r in reqs)
    assert len(heads) == 4
    counts = sorted(heads.values(), reverse=True)
    assert counts[0] > counts[-1]


def test_corpus_lengths_are_one_multiset():
    tr = {**load("finetune-pad128"), "rows": 2000}
    a = sorted(len(t) for t, _ in loadgen.corpus(tr, 1, 21128, 6))
    b = sorted(len(t) for t, _ in loadgen.corpus(tr, 99, 21128, 6))
    assert a == b
    assert loadgen.corpus(tr, 1, 21128, 6) == loadgen.corpus(tr, 1, 21128, 6)
    fill = sum(n + 2 for n in a) / (len(a) * 128)
    assert 0.33 < fill < 0.36          # the traffic file says 34.4 %
    assert max(a) <= 126 and min(a) >= 2


def test_closed_loop_cycles_one_multiset_without_shared_prefixes():
    tr = load("decode-file-saturated-mixedout")
    src = loadgen.closed_loop_prompts(tr, 4, 21128)
    first = [next(src) for _ in range(tr["cycle"])]
    second = [next(src) for _ in range(tr["cycle"])]
    assert sorted(len(p) for p, _ in first) == sorted(len(p) for p, _ in second)
    assert [len(p) for p, _ in first] != [len(p) for p, _ in second]
    assert all(64 <= len(p) <= 256 for p, _ in first)
    assert len({tuple(p[:16]) for p, _ in first}) == len(first)


def cycles(tr, seed, n=2):
    src = loadgen.closed_loop_prompts(tr, seed, 21128)
    return [[next(src) for _ in range(tr["cycle"])] for _ in range(n)]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 12345])
def test_answer_lengths_are_a_multiset_of_their_own(seed):
    """``new_tokens`` as a distribution: every cycle of every seed holds the
    same answer lengths, in an order that is neither another seed's nor the
    prompts'."""
    tr = load("decode-file-saturated-mixedout")
    base = cycles(tr, 0)
    got = cycles(tr, seed)
    news = lambda cyc: [k for _, k in cyc]
    for cyc in got:
        assert sorted(news(cyc)) == sorted(news(base[0]))
        assert news(cyc) != news(base[0])
        assert all(len(p) + k <= 512 for p, k in cyc)
    assert news(got[0]) != news(got[1])
    ks = news(got[0])
    # the chat cell's answer shape, letter for letter
    assert tr["new_tokens"] == load("decode-chat-belowknee")["answer_tokens"]
    assert min(ks) >= 16 and max(ks) <= 128
    assert abs(sum(ks) / len(ks) - 69.5) < 0.5 and len(set(ks)) > 60
    # shuffled independently of the prompts: long prompts do not get the
    # long answers
    by_prompt = [k for _, k in sorted(got[0], key=lambda pk: len(pk[0]))]
    assert by_prompt != sorted(ks) and by_prompt != sorted(ks, reverse=True)


def test_a_number_of_new_tokens_still_works():
    tr = load("decode-longdoc-saturated")
    assert isinstance(tr["new_tokens"], int)
    first, = cycles({**tr, "cycle": 8}, 3, n=1)
    assert [k for _, k in first] == [tr["new_tokens"]] * 8


def test_vocabulary_has_the_published_rows():
    lines = loadgen.vocab_lines(21128)
    assert len(lines) == 21128 == len(set(lines))
    assert lines[:5] == ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
