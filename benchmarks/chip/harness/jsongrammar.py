"""Byte-level JSON, written out as a pushdown recognizer of its own.

The language is the one the served `json` grammar states: RFC 8259
values (object, array, string, number, true, false, null) with
whitespace (space, tab, CR, LF) between tokens, and byte-level strings:
any byte but `"` and `\\` stands for itself, and `\\` escapes any byte
but LF. No unicode validation.

A state is a hashable tuple (mode, stack, extra); `step` returns the
next state or None. `prefix_ok` says whether a byte string is a prefix
of some JSON text, `complete` whether it is one. The benchmark's
comparison uses these to decide which tokens were allowed at a served
position; nothing here comes from the program.
"""
from __future__ import annotations

import numpy as np

WS = frozenset(b" \t\r\n")
DIGITS = frozenset(b"0123456789")
_LIT = {ord("t"): b"rue", ord("f"): b"alse", ord("n"): b"ull"}

# modes
VALUE = 0          # a value is due
VALUE_OR_END = 1   # just after '[': a value or ']'
KEY_OR_END = 2     # just after '{': a key string or '}'
KEY = 3            # after ',' in an object: a key string
COLON = 4          # after a key
AFTER = 5          # after a complete value
STR = 6            # inside a string (extra: True for a key)
ESC = 7            # after a backslash in a string (extra: is key)
LIT = 8            # inside a literal (extra: bytes still due)
NUM = 9            # inside a number (extra: number sub-state)

# number sub-states; the complete ones may end the number
N_MINUS, N_ZERO, N_INT, N_DOT, N_FRAC, N_E, N_ESIGN, N_EXP = range(8)
_NUM_DONE = frozenset((N_ZERO, N_INT, N_FRAC, N_EXP))

START = (VALUE, (), None)


def _start_value(stack, b):
    if b == ord('"'):
        return (STR, stack, False)
    if b == ord("{"):
        return (KEY_OR_END, stack + ("o",), None)
    if b == ord("["):
        return (VALUE_OR_END, stack + ("a",), None)
    if b == ord("-"):
        return (NUM, stack, N_MINUS)
    if b == ord("0"):
        return (NUM, stack, N_ZERO)
    if b in DIGITS:
        return (NUM, stack, N_INT)
    if b in _LIT:
        return (LIT, stack, _LIT[b])
    return None


def _num(sub, b):
    if sub == N_MINUS:
        return N_ZERO if b == ord("0") else (N_INT if b in DIGITS else None)
    if sub == N_ZERO:
        return N_DOT if b == ord(".") else (N_E if b in b"eE" else None)
    if sub == N_INT:
        if b in DIGITS:
            return N_INT
        return N_DOT if b == ord(".") else (N_E if b in b"eE" else None)
    if sub == N_DOT:
        return N_FRAC if b in DIGITS else None
    if sub == N_FRAC:
        return N_FRAC if b in DIGITS else (N_E if b in b"eE" else None)
    if sub == N_E:
        return N_ESIGN if b in b"+-" else (N_EXP if b in DIGITS else None)
    if sub in (N_ESIGN, N_EXP):
        return N_EXP if b in DIGITS else None
    return None


def step(state, b: int):
    mode, stack, extra = state
    if mode == STR:
        if b == ord('"'):
            return (COLON, stack, None) if extra else (AFTER, stack, None)
        if b == ord("\\"):
            return (ESC, stack, extra)
        return state
    if mode == ESC:
        return None if b == ord("\n") else (STR, stack, extra)
    if mode == LIT:
        if b != extra[0]:
            return None
        return (LIT, stack, extra[1:]) if len(extra) > 1 else \
            (AFTER, stack, None)
    if mode == NUM:
        nxt = _num(extra, b)
        if nxt is not None:
            return (NUM, stack, nxt)
        if extra not in _NUM_DONE:
            return None
        return step((AFTER, stack, None), b)
    if b in WS:
        return state
    if mode == VALUE:
        return _start_value(stack, b)
    if mode == VALUE_OR_END:
        if b == ord("]"):
            return (AFTER, stack[:-1], None)
        return _start_value(stack, b)
    if mode in (KEY_OR_END, KEY):
        if b == ord('"'):
            return (STR, stack, True)
        if mode == KEY_OR_END and b == ord("}"):
            return (AFTER, stack[:-1], None)
        return None
    if mode == COLON:
        return (VALUE, stack, None) if b == ord(":") else None
    if mode == AFTER:
        if not stack:
            return None
        top = stack[-1]
        if b == ord(","):
            return (KEY, stack, None) if top == "o" else (VALUE, stack, None)
        if (top == "o" and b == ord("}")) or (top == "a" and b == ord("]")):
            return (AFTER, stack[:-1], None)
        return None
    return None


def feed(state, data: bytes):
    for b in data:
        state = step(state, b)
        if state is None:
            return None
    return state


def prefix_ok(data: bytes) -> bool:
    return feed(START, data) is not None


def is_complete(state) -> bool:
    if state is None:
        return False
    mode, stack, extra = state
    if stack:
        return False
    return mode == AFTER or (mode == NUM and extra in _NUM_DONE)


def complete(data: bytes) -> bool:
    return is_complete(feed(START, data))


class Allowed:
    """Which token ids a JSON state allows, over one vocabulary."""

    def __init__(self, token_bytes: list, eos_id: int):
        self.token_bytes = token_bytes
        self.eos_id = eos_id
        self.first = np.array([tb[0] if tb else -1 for tb in token_bytes],
                              np.int32)

    def first_ok(self, state) -> np.ndarray:
        """[V] bool: tokens whose first byte the state takes (specials,
        which have no bytes, are never allowed here)."""
        ok_byte = np.array([step(state, b) is not None
                            for b in range(256)] + [False])
        return ok_byte[self.first]          # first == -1 -> index 256

    def allows(self, state, tid: int) -> bool:
        if tid == self.eos_id:
            return is_complete(state)
        tb = self.token_bytes[tid]
        return bool(tb) and feed(state, tb) is not None

    def best(self, state, scores: np.ndarray, first_ok=None,
             above: float = -np.inf):
        """Highest-scoring allowed token id among those scoring above
        `above`, or None: candidates are tried from the top down."""
        if first_ok is None:
            first_ok = self.first_ok(state)
        cand = np.nonzero(first_ok & (scores > above))[0]
        order = cand[np.argsort(-scores[cand], kind="stable")]
        eos_ok = is_complete(state) and scores[self.eos_id] > above
        for tid in order:
            if eos_ok and scores[self.eos_id] >= scores[tid]:
                return self.eos_id
            if feed(state, self.token_bytes[tid]) is not None:
                return int(tid)
        return self.eos_id if eos_ok else None
