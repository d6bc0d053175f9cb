"""The served vocabulary, rebuilt from its published construction.

The program serves a deterministic BPE-shaped byte vocabulary (256 byte
tokens plus keyword, punctuation, digit and letter merges, then
zero-padded digit strings as filler up to the vocabulary size). The
reference side of the benchmark needs the bytes of every token id and a
greedy longest-match encoder, and imports nothing of the program, so the
construction is written out here. `run.py` checks that the program
tokenized every prompt to the ids this encoder gives.

ids: 0 = PAD, 1 = EOS, 2 = BOS, 3..258 = single bytes, 259.. = merges.
"""
from __future__ import annotations

PAD_ID, EOS_ID, BOS_ID = 0, 1, 2
NUM_SPECIAL = 3

_KEYWORDS = [
    "true", "false", "null", "fn", "let", "if", "else", "while", "for",
    "in", "return", "break", "continue", "struct", "int", "float", "str",
    "bool", "nil", "math_exp", "math_sqrt", "math_sin", "math_cos", "math",
    "select", "from", "where", "group", "by", "order", "having", "limit",
    "join", "on", "as", "and", "or", "not", "count", "sum", "avg", "min",
    "max", "distinct", "between", "like", "exists", "union", "left",
    "right", "inner", "asc", "desc", "offset", "is",
    "SELECT", "FROM", "WHERE", "GROUP", "BY", "ORDER", "HAVING", "LIMIT",
    "JOIN", "ON", "AS", "AND", "OR", "NOT", "COUNT", "SUM", "AVG", "MIN",
    "MAX", "DISTINCT", "BETWEEN", "LIKE", "EXISTS", "UNION",
    "name", "value", "type", "id", "key", "data", "list", "item", "index",
    "result", "args", "len", "total", "self", "this", "print", "range",
    "def", "class", "elif", "pass", "None", "True", "False", "import",
    "lambda", "yield", "def ", "return ", "    ",
]
_PUNCT = [
    '":', '",', '" ', ' "', '{"', '"}', '):', ');', ')(', '()', '())',
    '();', '[]', '{}', '))', '((', '],', '};', ', ', ': ', '; ', ' (',
    ' )', ' {', ' }', ' [', ' ]', ' =', '= ', ' == ', ' != ', ' <= ',
    ' >= ', ' < ', ' > ', ' + ', ' - ', ' * ', ' / ', ' && ', ' || ',
    '->', '=>', '//', '/*', '*/', '\n\n', '\n  ', '\n    ', '    ',
    '  ', '."', '".', '...', 'e+', 'e-', 'E+', '0.', '1.', '("', '")',
]


def _merges(n: int) -> list[bytes]:
    out: list[bytes] = []
    seen: set[bytes] = set()

    def add(s: str) -> None:
        b = s.encode()
        if len(b) >= 2 and b not in seen:
            seen.add(b)
            out.append(b)

    for kw in _KEYWORDS:
        add(kw)
        add(" " + kw)
    for p in _PUNCT:
        add(p)
    digits = "0123456789"
    for a in digits:
        for b in digits:
            add(a + b)
    letters = "etaoinshrdlucmfwypvbgkqjxz"
    for a in letters:
        for b in letters:
            add(a + b)
    for a in letters[:12]:
        add(" " + a)
    for a in letters[:12]:
        for b in letters[:12]:
            for c in letters[:12]:
                add(a + b + c)
                if len(out) > n:
                    return out
    i = 0
    while len(out) <= n:
        add(f"{i:04d}")
        i += 1
    return out


class Vocab:
    """Token bytes and a greedy longest-match encoder for one size."""

    def __init__(self, size: int):
        base = [b"", b"", b""] + [bytes([b]) for b in range(256)]
        self.size = size
        self.token_bytes = base + _merges(size - len(base))[:size - len(base)]
        if len(self.token_bytes) != size:
            raise ValueError(f"vocabulary of {size} cannot be built")
        self._trie: dict = {}
        for tid, tb in enumerate(self.token_bytes[NUM_SPECIAL:],
                                 NUM_SPECIAL):
            node = self._trie
            for ch in tb:
                node = node.setdefault(ch, {})
            node[-1] = tid

    def encode(self, data: bytes) -> list[int]:
        ids, i, n = [], 0, len(data)
        while i < n:
            node, best, j = self._trie, None, i
            while j < n and data[j] in node:
                node = node[data[j]]
                j += 1
                if -1 in node:
                    best = (node[-1], j)
            tid, i = best
            ids.append(tid)
        return ids

    def decode(self, ids) -> bytes:
        return b"".join(self.token_bytes[int(t)] for t in ids)
