"""The reference JSON grammar against Python's own JSON parser, where
their languages meet (ASCII without control characters)."""
import json
import random
import re

import tiny  # noqa: F401
from harness import jsongrammar as J
from harness.vocab import EOS_ID, Vocab


def _docs(n):
    rng = random.Random(0)

    def value(d):
        k = rng.randrange(7 if d < 3 else 4)
        if k == 0:
            return rng.choice([True, False, None])
        if k == 1:
            return rng.choice([0, -1, 12, 3.5, -0.25e-3, 1e21])
        if k == 2:
            return "".join(rng.choice('ab "\\/') for _ in range(4))
        if k == 3:
            return rng.randrange(100)
        if k in (4, 5):
            return [value(d + 1) for _ in range(rng.randrange(3))]
        return {f"k{i}": value(d + 1) for i in range(rng.randrange(3))}
    return [json.dumps(value(0), indent=rng.choice([None, 1]))
            for _ in range(n)]


def test_agrees_with_the_json_module():
    for doc in _docs(300):
        b = doc.encode()
        assert J.complete(b), doc
        assert all(J.prefix_ok(b[:i]) for i in range(len(b) + 1)), doc
    rng = random.Random(1)
    for doc in _docs(300):
        s = list(doc)
        s[rng.randrange(len(s))] = rng.choice('{}[]:,"0e.-tx \\')
        s = "".join(s)
        if re.search(r'\\[^"\\/bfnrtu]', s):
            continue        # any escape is allowed byte-level, not in json
        try:
            json.loads(s)
            ok = True
        except ValueError:
            ok = False
        assert J.complete(s.encode()) == ok, s


def test_byte_level_strings():
    assert J.complete(b'"\xff\n\t"')          # raw bytes stand for themselves
    assert J.complete(b'"\\q"')               # any escaped byte but LF
    assert not J.prefix_ok(b'"\\\n')


def test_allowed_best_walks_down_to_the_first_allowed_token():
    import numpy as np
    v = Vocab(1024)
    allowed = J.Allowed(v.token_bytes, EOS_ID)
    state = J.feed(J.START, b'{"a": ')
    scores = np.zeros(v.size)
    x = v.encode(b"x")[0]
    t = v.encode(b"true")[0]
    scores[x], scores[t] = 5.0, 3.0
    assert allowed.best(state, scores) == t
    assert allowed.best(state, scores, above=3.0) is None
    done = J.feed(J.START, b"[1]")
    scores[EOS_ID] = 1.0
    assert allowed.best(done, scores) == EOS_ID
    assert not allowed.allows(state, EOS_ID)
