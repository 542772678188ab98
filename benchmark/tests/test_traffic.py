import itertools

import numpy as np

from benchmark import harness
from benchmark.traffic import lm_tokens, mlm_nsp, open_loop

CHAT = harness.load_json("tests", "serve-toy.json")["traffic"]
SIZE = {"vocab_size": 50257}


def _key(reqs):
    return [(r.due, tuple(r.prompt), r.max_new_tokens) for r in reqs]


def test_open_loop_same_seed_same_requests_other_seed_other_tokens():
    a = open_loop.requests(7, CHAT, SIZE, 20)
    b = open_loop.requests(7, CHAT, SIZE, 20)
    c = open_loop.requests(8, CHAT, SIZE, 20)
    assert _key(a) == _key(b)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_open_loop_every_seed_carries_the_same_work_in_another_order():
    a = open_loop.requests(7, CHAT, SIZE, 20)
    c = open_loop.requests(2 ** 31 + 11, CHAT, SIZE, 20)
    assert len(a) == len(c) == round(CHAT["rate_per_s"] * 20)
    plan = lambda rs: [(r.due, len(r.prompt), r.max_new_tokens) for r in rs]
    assert plan(a) != plan(c)
    for part in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(part, a)) == sorted(map(part, c))
    gaps = lambda rs: sorted(np.diff([0.0] + [r.due for r in rs]).round(9))
    assert gaps(a) == gaps(c)
    assert all(0 < r.due < 20 for r in a)
    assert [r.due for r in a] == sorted(r.due for r in a)
    p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in a)
    lens = sorted(len(r.prompt) for r in a)
    assert lens[len(lens) // 2 - 1] <= p["median"] <= lens[len(lens) // 2]
    assert len(open_loop.requests(7, CHAT, SIZE, 40)) == 2 * len(a)


def test_training_batches_follow_the_seed_and_all_rows_differ():
    cell = {"micro": 4, "chips": 1, "seq": 16, "mlm_share": 0.15}
    for gen in (lm_tokens, mlm_nsp):
        a = list(itertools.islice(gen.micro_batches(3, cell, SIZE), 3))
        b = list(itertools.islice(gen.micro_batches(3, cell, SIZE), 3))
        c = list(itertools.islice(gen.micro_batches(4, cell, SIZE), 3))
        for x, y in zip(a, b):
            assert all(np.array_equal(x[k], y[k]) for k in x)
        assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
        rows = np.concatenate([x["input_ids"] for x in a])
        assert len({tuple(r) for r in rows}) == len(rows)
        assert gen.tokens_per_micro_batch(cell) == 64
    labels = a[0]["masked_lm_labels"]
    assert ((labels == -1) | (labels == a[0]["input_ids"])).all()
