"""Device regex lane: DFA compiler exactness vs `re`, kernel integration,
overflow fallback, and end-to-end agreement with the CPU oracle on
regex-heavy corpora."""

import random
import re as re_mod

import numpy as np
import pytest

from authorino_tpu.compiler import ConfigRules, compile_corpus, encode_batch
from authorino_tpu.compiler.compile import OP_CPU, OP_REGEX_DFA
from authorino_tpu.compiler.redfa import compile_regex_dfa
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.compiler.pack import pack_batch
from authorino_tpu.ops import eval_batch_jit, to_device

from test_compiler_differential import oracle_verdict

PATTERNS = [
    r"^/pets/\d+$", r"\d+", r"^(GET|POST)$", r"adm.n", r"^$", r"abc",
    r"^/api/v\d+/r\d", r"[a-f0-9]{4}", r"a+b*c?", r"(foo|bar)+baz",
    r"^x[^y]z$", r"^\w+@\w+\.\w+$", r"a{2,4}", r"^-?\d+(\.\d+)?$",
    r"(?:ab|cd)ef", r"^Bearer ", r"\.json$", r"^[A-Z][a-z]+$",
]

STRINGS = ["", "/pets/1", "/pets/123x", "GET", "POST", "PUT", "admin", "admon",
           "abc", "xabcx", "/api/v2/r3", "deadbeef", "aabbc", "foobarbaz",
           "xaz", "a@b.co", "aa", "aaaaa", "42", "-3.14", "abef", "cdef",
           "Bearer tok", "data.json", "Hello", "hello", "x" * 200]


def dfa_match(dfa, s: str):
    bs = s.encode("utf-8")
    st = dfa.start
    for b in bs:
        st = int(dfa.trans[st, b])
    return bool(dfa.accept[st])


def test_dfa_compiler_exact_vs_re():
    for p in PATTERNS:
        dfa = compile_regex_dfa(p)
        assert dfa is not None, f"pattern unexpectedly unsupported: {p}"
        gold = re_mod.compile(p)
        for s in STRINGS:
            assert dfa_match(dfa, s) == (gold.search(s) is not None), (p, s)


def test_unsupported_patterns_fall_back():
    # backreferences / lookaheads are not RE2 (the reference rejects them
    # too); unicode classes and repeats past RE2's own 1000 exceed the
    # device subset
    assert compile_regex_dfa(r"x{1001}") is None
    assert compile_regex_dfa(r"(?=foo)") is None


def test_kernel_uses_dfa_lane():
    configs = [
        ConfigRules("c", evaluators=[(None, Pattern("path", Operator.MATCHES, r"^/pets/\d+$"))]),
    ]
    policy = compile_corpus(configs)
    assert (policy.leaf_op == OP_REGEX_DFA).any()
    assert policy.n_byte_attrs == 1
    params = to_device(policy)
    docs = [{"path": "/pets/1"}, {"path": "/pets/x"}, {"path": "/pets/123"}, {"path": ""}]
    enc = encode_batch(policy, docs, [0] * 4)
    # the CPU lane must NOT have been consulted for in-range values
    assert not enc.cpu_lane.any()
    own, _ = eval_batch_jit(params, pack_batch(policy, enc))
    assert list(own) == [True, False, True, False]


def test_long_value_overflow_falls_back_to_cpu():
    configs = [
        ConfigRules("c", evaluators=[(None, Pattern("v", Operator.MATCHES, r"needle$"))]),
    ]
    policy = compile_corpus(configs)
    long_hit = "x" * 300 + "needle"        # > DFA_VALUE_BYTES
    long_miss = "x" * 300
    nul_hit = "a\x00needle"                # NUL byte → CPU lane
    docs = [{"v": long_hit}, {"v": long_miss}, {"v": nul_hit}, {"v": "short needle"}]
    enc = encode_batch(policy, docs, [0] * 4)
    assert enc.byte_ovf[:3, 0].all() and not enc.byte_ovf[3, 0]
    own, _ = eval_batch_jit(to_device(policy), pack_batch(policy, enc))
    assert list(own) == [True, False, True, True]


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_regex_heavy_corpus_matches_oracle(seed):
    rng = random.Random(seed)
    configs = []
    for i in range(8):
        pats = [
            Pattern("path", Operator.MATCHES, rng.choice(PATTERNS)),
            Pattern("name", Operator.MATCHES, rng.choice(PATTERNS)),
            Pattern("tag", Operator.EQ, rng.choice(["a", "b"])),
        ]
        comb = All if rng.random() < 0.5 else Any_
        configs.append(ConfigRules(f"cfg-{i}", evaluators=[(None, comb(*pats))]))
    policy = compile_corpus(configs)
    params = to_device(policy)
    docs = [
        {"path": rng.choice(STRINGS), "name": rng.choice(STRINGS), "tag": rng.choice(["a", "b", "c"])}
        for _ in range(48)
    ]
    rows = [rng.randrange(len(configs)) for _ in docs]
    enc = encode_batch(policy, docs, rows)
    own, _ = eval_batch_jit(params, pack_batch(policy, enc))
    for r, (doc, row) in enumerate(zip(docs, rows)):
        assert bool(own[r]) == oracle_verdict(configs[row], doc), (seed, r, doc)


def test_determinization_memo_keys_distinguish_anchoring():
    """Audit of the process-wide determinization memo (compiler/redfa.py
    _DFA_MEMO): the key is the FULL pattern string, and anchoring lives in
    the pattern string itself (``^``/``$`` prefixes/suffixes), so variants
    of one body can never share an entry.  There is no flags parameter in
    the API at all — nothing else can alias.  Regression-pins both the
    isolation (distinct languages per variant) and the memo behaviour
    (same pattern → the SAME immutable DFA object, cross-snapshot)."""
    variants = ["abc", "^abc", "abc$", "^abc$"]
    dfas = {p: compile_regex_dfa(p) for p in variants}
    assert all(d is not None for d in dfas.values())
    # each anchoring variant decides a different language on these probes
    probes = ["abc", "xabc", "abcx", "xabcx", ""]
    behaviours = {p: tuple(dfa_match(d, s) for s in probes)
                  for p, d in dfas.items()}
    assert len(set(behaviours.values())) == len(variants), behaviours
    assert behaviours["abc"] == (True, True, True, True, False)
    assert behaviours["^abc"] == (True, False, True, False, False)
    assert behaviours["abc$"] == (True, True, False, False, False)
    assert behaviours["^abc$"] == (True, False, False, False, False)
    # memo hit: byte-identical pattern returns the identical object (what
    # lets the compiler's table dedup collapse repeats across snapshots)
    for p in variants:
        assert compile_regex_dfa(p) is dfas[p]
    # ...and an escaped trailing dollar is NOT treated as an end anchor
    esc = compile_regex_dfa(r"abc\$")
    assert esc is not None and esc is not dfas["abc$"]
    assert dfa_match(esc, "abc$x") and not dfa_match(esc, "abc")
