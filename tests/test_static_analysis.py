"""Compile-time verification subsystem (ISSUE 4, analysis/).

Three layers under test: the tensor-IR lint (clean compiles pass; each
hand-corrupted snapshot trips EXACTLY its intended finding kind), the
Cedar-style policy semantic analysis (plants are found, sound rules are
not flagged), and the async-hazard code lint — including the tier-1 gate
that the repo itself stays finding-free.  Plus the --strict-verify swap
rejection (old generation keeps serving) and the packer's typed PackError.

Deliberately import-light: collects on images without `cryptography`
(no evaluators.identity / native_frontend imports)."""

from __future__ import annotations

import json
import random
from copy import deepcopy

import numpy as np
import pytest

from authorino_tpu.analysis.code_lint import lint_paths, lint_source
from authorino_tpu.analysis.fixtures import (
    finding_fixture_configs,
    fixture_configs,
    fixture_policy,
)
from authorino_tpu.analysis.policy_analysis import (
    MAX_ATOMS,
    analyze_hosts,
    analyze_policy,
    analyze_snapshot,
)
from authorino_tpu.analysis.tensor_lint import (
    lint_device_batch,
    lint_scatter_plan,
    lint_snapshot,
    tensor_lint,
)
from authorino_tpu.compiler import ConfigRules, compile_corpus
from authorino_tpu.compiler.encode import encode_batch_py
from authorino_tpu.compiler.pack import (
    PackError,
    batch_row_keys,
    dedup_rows,
    pack_batch,
)
from authorino_tpu.expressions import All, Any_, Operator, Pattern
from authorino_tpu.runtime import EngineEntry, PolicyEngine
from authorino_tpu.runtime.engine import SnapshotRejected


def _random_corpus(seed: int, n_configs: int = 7):
    """A generated corpus: every operator, ~regex mix,
    nested And/Or, shared + unique constants."""
    rng = random.Random(seed)
    configs = []
    for i in range(n_configs):
        pats = [
            Pattern("request.method", Operator.EQ,
                    rng.choice(["GET", "POST"])),
            Pattern("auth.identity.org", Operator.EQ, f"org-{i}"),
        ]
        for j in range(rng.randrange(1, 6)):
            kind = rng.random()
            if kind < 0.15:
                pats.append(Pattern("request.url_path", Operator.MATCHES,
                                    rf"^/api/v\d+/r{j}"))
            elif kind < 0.45:
                pats.append(Pattern("auth.identity.roles", Operator.INCL,
                                    f"role-{rng.randrange(6)}"))
            elif kind < 0.65:
                pats.append(Pattern("auth.identity.groups", Operator.EXCL,
                                    f"banned-{rng.randrange(4)}"))
            else:
                pats.append(Pattern(f"request.headers.x-{rng.randrange(3)}",
                                    Operator.NEQ, f"v-{rng.randrange(5)}"))
        rule = All(pats[0], Any_(*pats[1:]))
        cond = (Pattern("request.host", Operator.EQ, f"h{i}")
                if rng.random() < 0.4 else None)
        configs.append(ConfigRules(name=f"cfg-{i}",
                                   evaluators=[(cond, rule)]))
    return configs


def _docs(seed: int, n: int):
    rng = random.Random(seed)
    return [
        {
            "request": {"method": rng.choice(["GET", "POST"]),
                        "url_path": rng.choice(["/api/v1/r0", "/x"]),
                        "host": f"h{rng.randrange(4)}",
                        "headers": {f"x-{k}": f"v-{rng.randrange(5)}"
                                    for k in range(3)}},
            "auth": {"identity": {
                "org": f"org-{rng.randrange(8)}",
                "roles": [f"role-{rng.randrange(6)}"
                          for _ in range(rng.randrange(3))],
                "groups": [f"banned-{rng.randrange(4)}"
                           for _ in range(rng.randrange(2))],
            }},
        }
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# tensor lint: property (generated corpora pass) + targeted corruptions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_generated_corpora_pass_tensor_lint(seed):
    policy = compile_corpus(_random_corpus(seed), members_k=8)
    assert tensor_lint(policy) == []
    docs = _docs(seed, 12)
    rows = [random.Random(seed).randrange(policy.n_configs and 7)
            for _ in docs]
    enc = encode_batch_py(policy, docs, rows, batch_pad=16)
    db = pack_batch(policy, enc)
    assert lint_device_batch(policy, db) == []
    keys = batch_row_keys(db, len(docs))
    all_rows = list(range(len(docs)))
    unique_rows, inverse = dedup_rows(keys, all_rows)
    assert lint_scatter_plan(keys, all_rows, unique_rows, inverse) == []


def test_fixture_policy_clean():
    assert tensor_lint(fixture_policy()) == []


def test_corrupt_dfa_table_index():
    p = deepcopy(fixture_policy())
    p.dfa_table_of_row = p.dfa_table_of_row.copy()
    p.dfa_table_of_row[0] = p.dfa_tables.shape[0] + 3
    kinds = {f.kind for f in tensor_lint(p)}
    assert kinds == {"dfa-table-index"}


def test_corrupt_cyclic_circuit():
    p = deepcopy(fixture_policy())
    ch0 = p.levels[0][0].copy()
    ch0[0, 0] = p.buffer_size - 1  # forward reference = cycle
    p.levels = ((ch0, p.levels[0][1]),) + p.levels[1:]
    kinds = {f.kind for f in tensor_lint(p)}
    assert kinds == {"circuit-order"}


def test_corrupt_scatter_map():
    keys = [b"a", b"b", b"a", b"c"]
    rows = [0, 1, 2, 3]
    # row 2 (key a) wrongly fans out from unique slot 1 (key b)
    bad = np.array([0, 1, 1, 2])
    kinds = {f.kind for f in lint_scatter_plan(keys, rows, [0, 1, 3], bad)}
    assert kinds == {"scatter-cover"}
    # and the real dedup plan passes
    unique_rows, inverse = dedup_rows(keys, rows)
    assert lint_scatter_plan(keys, rows, unique_rows, inverse) == []


def test_corrupt_dfa_next_state():
    p = deepcopy(fixture_policy())
    p.dfa_tables = p.dfa_tables.copy()
    p.dfa_tables[0, 0, 0] = 255  # way past S
    kinds = {f.kind for f in tensor_lint(p)}
    assert kinds == {"dfa-next-state"}


def test_corrupt_eval_table_range():
    p = deepcopy(fixture_policy())
    p.eval_rule = p.eval_rule.copy()
    p.eval_rule[0, 0] = p.buffer_size + 10
    kinds = {f.kind for f in tensor_lint(p)}
    assert kinds == {"operand-range"}


@pytest.mark.parametrize("corruption", ["row-dropped", "foreign-row",
                                        "unsorted", "wrong-shape"])
def test_corrupt_own_dfa_rows(corruption):
    """config_dfa_rows[g] must be exactly the DFA rows config g's evaluators
    reach: the served kernel scans nothing else for g's requests."""
    rx = [Pattern("request.url_path", Operator.MATCHES, rf"^/t{i}/")
          for i in range(4)]
    p = compile_corpus([
        ConfigRules(name="two", evaluators=[(rx[0], All(rx[1], rx[2]))]),
        ConfigRules(name="one", evaluators=[(None, Any_(rx[1], rx[3]))]),
        ConfigRules(name="none", evaluators=[
            (None, Pattern("request.method", Operator.EQ, "GET"))])])
    table = p.config_dfa_rows.copy()
    assert table.tolist() == [[0, 1, 2], [1, 3, -1], [-1, -1, -1]]
    assert tensor_lint(p) == []
    if corruption == "row-dropped":
        table[1, 1] = -1
    elif corruption == "foreign-row":
        table[2, 0] = 3
    elif corruption == "unsorted":
        table[0, :2] = table[0, :2][::-1]
    else:
        table = table[:-1]
    p.config_dfa_rows = table
    kinds = {f.kind for f in tensor_lint(p)}
    assert kinds == {"own-dfa-rows"}


# ---------------------------------------------------------------------------
# packer: typed PackError instead of silent clamp/wrap
# ---------------------------------------------------------------------------


def test_pack_error_member_grid_overflow():
    policy = fixture_policy()
    enc = encode_batch_py(policy, _docs(1, 2), [0, 1], batch_pad=2)
    bad = deepcopy(policy)
    bad.n_member_attrs = max(bad.member_attrs.shape[0] - 1, 0)
    with pytest.raises(PackError, match="padded grid"):
        pack_batch(bad, enc)
    # tensor lint agrees the same policy is invalid
    assert any(f.kind == "operand-range"
               for f in tensor_lint(bad, check_lanes=False))


def test_pack_error_int16_wraparound():
    policy = fixture_policy()
    assert len(policy.interner) < 32767  # int16 wire dtype in effect
    enc = encode_batch_py(policy, _docs(2, 2), [0, 1], batch_pad=2)
    # an int32-encoded batch (the sharded encode contract) carrying an id
    # past the int16 wire range: .astype(int16) would silently WRAP it to a
    # negative id — a wrong operand, not an error — before this check
    enc.attrs_val = enc.attrs_val.astype(np.int32)
    enc.attrs_val[0, 0] = 40_000
    with pytest.raises(PackError, match="int16"):
        pack_batch(policy, enc)


# ---------------------------------------------------------------------------
# policy semantic analysis
# ---------------------------------------------------------------------------


def test_policy_analysis_finds_planted_kinds():
    findings, summary = analyze_policy(
        compile_corpus(finding_fixture_configs()))
    kinds = {f.kind for f in findings}
    assert {"constant-allow", "constant-deny", "shadowed-rule",
            "duplicate-rule"} <= kinds
    assert summary["configs"] == 3
    # the shadowed finding names its shadower
    sh = next(f for f in findings if f.kind == "shadowed-rule")
    assert sh.detail["shadowed_by"] == 0 and sh.detail["config"] == "blocked"


def test_policy_analysis_sound_rules_not_flagged():
    findings, _ = analyze_policy(compile_corpus(_random_corpus(7)))
    # generated rules mix eq/incl over distinct constants: satisfiable and
    # falsifiable, so the analyzer must stay quiet
    assert findings == []


def test_policy_analysis_complementary_atoms():
    eq = Pattern("a.b", Operator.EQ, "x")
    neq = Pattern("a.b", Operator.NEQ, "x")
    incl = Pattern("a.c", Operator.INCL, "y")
    excl = Pattern("a.c", Operator.EXCL, "y")
    taut = compile_corpus([ConfigRules(name="t", evaluators=[
        (None, Any_(eq, neq)), (None, Any_(incl, excl))])])
    findings, _ = analyze_policy(taut)
    assert [f.kind for f in findings] == ["constant-allow", "constant-allow"]
    # a condition gating an unsat rule: contribution ¬cond ∨ rule is NOT
    # constant (requests failing the condition pass) — must not be flagged
    # as constant-deny
    gated = compile_corpus([ConfigRules(name="g", evaluators=[
        (incl, All(eq, neq))])])
    findings, _ = analyze_policy(gated)
    assert "constant-deny" not in {f.kind for f in findings}


def test_policy_analysis_skips_wide_support():
    pats = [Pattern(f"a.k{i}", Operator.EQ, f"v{i}")
            for i in range(MAX_ATOMS + 2)]
    findings, summary = analyze_policy(
        compile_corpus([ConfigRules(name="wide",
                                    evaluators=[(None, Any_(*pats))])]))
    assert findings == []
    assert summary["skipped_wide"] == 1


def test_duplicate_host_detection():
    class E:
        def __init__(self, id_, hosts):
            self.id, self.hosts = id_, hosts

    findings = analyze_hosts([E("ns/a", ["x.com", "y.com"]),
                              E("ns/b", ["y.com"]),
                              E("ns/c", [])])
    assert [f.kind for f in findings] == ["duplicate-host"]
    assert findings[0].detail["host"] == "y.com"
    assert findings[0].detail["configs"] == ["ns/a", "ns/b"]


# ---------------------------------------------------------------------------
# async-hazard code lint
# ---------------------------------------------------------------------------


_PLANTED = '''
import time, jax, threading
from functools import partial

async def a1():
    time.sleep(1)

async def a2(lock):
    lock.acquire()

async def ok_awaited(sem):
    await sem.acquire()

async def a3(self):
    with self._queue_lock:
        await later()

async def ok_lock_no_await(self):
    with self._queue_lock:
        x = 1

@jax.jit
def a4(x):
    if x > 0:
        return x
    return -x

@partial(jax.jit, static_argnames=())
def ok_static(params, x):
    if params["t"] is not None:
        return x
    if x.shape[0] > 2:
        return x
    return x

def a5():
    try:
        pass
    except:
        pass

async def ok_suppressed():
    time.sleep(1)  # lint-ok: blocking-in-async -- startup-only

async def ok_nested_sync():
    def helper():
        time.sleep(1)
    return helper
'''


def test_code_lint_planted_hazards():
    kinds = [f.kind for f in lint_source(_PLANTED, "planted.py")]
    assert sorted(kinds) == ["bare-except", "blocking-in-async",
                             "blocking-in-async", "lock-across-await",
                             "tracer-branch"]
    lines = {f.kind: f.location for f in lint_source(_PLANTED, "p.py")}
    assert lines["lock-across-await"].endswith(":15")


def test_code_lint_await_after_nested_def():
    # a nested def must prune only ITS subtree: an await elsewhere in the
    # same compound statement still counts (review-found false negative)
    src = (
        "async def f(self, fast):\n"
        "    with self._lock:\n"
        "        if fast:\n"
        "            def helper():\n"
        "                pass\n"
        "        else:\n"
        "            await later()\n"
    )
    assert [f.kind for f in lint_source(src)] == ["lock-across-await"]


def test_code_lint_static_accessor_prunes_only_its_subtree():
    # `.shape` makes y.shape[0] static, but x is still a traced param in
    # the same compare side (review-found false negative)
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, y):\n"
        "    if x + y.shape[0] > 3:\n"
        "        return x\n"
        "    return y\n"
    )
    assert [f.kind for f in lint_source(src)] == ["tracer-branch"]


def test_code_lint_suppression_scopes():
    src = "async def f():\n    import time\n    time.sleep(1)  # lint-ok\n"
    assert lint_source(src) == []
    src = ("async def f():\n    import time\n"
           "    time.sleep(1)  # lint-ok: tracer-branch\n")
    # wrong kind in the suppression: the finding survives
    assert [f.kind for f in lint_source(src)] == ["blocking-in-async"]
    assert lint_source("# lint: skip-file\nasync def f():\n"
                       "    import time\n    time.sleep(1)\n") == []


def test_code_lint_pickle_import_kind():
    """ISSUE 19 satellite: every on-disk artifact (snapshots, capture
    segments, the decision corpus) is a pickle-free checksummed
    container by design — a module-level pickle import outside tests/
    is a lint error, not a style choice."""
    src = "import pickle\nfrom cloudpickle import dumps\nimport dill\n"
    kinds = [f.kind for f in lint_source(src, "authorino_tpu/x.py")]
    assert kinds == ["pickle-import"] * 3
    # tests/ may unpickle fixtures; paths under tests/ are exempt
    assert lint_source(src, "tests/test_x.py") == []
    assert lint_source(src, "pkg/tests/helper.py") == []
    # suppressible only explicitly, with the usual reasoned syntax
    ok = "import pickle  # lint-ok: pickle-import -- trusted local cache\n"
    assert lint_source(ok, "authorino_tpu/x.py") == []
    # a RELATIVE `from .pickle import x` is someone's own module, not
    # stdlib pickle — no finding
    assert lint_source("from .pickle import x\n",
                       "authorino_tpu/x.py") == []


def test_code_lint_non_atomic_write_kind():
    """ISSUE 20 satellite: a durable artifact written with a bare
    ``open(path, "w")`` is a torn-write waiting for a SIGKILL — every
    durable writer must ride utils/atomicio.py (or hand-roll the same
    tmp + fsync + os.replace discipline)."""
    src = ("def dump(snapshot_path, blob):\n"
           "    with open(snapshot_path, 'wb') as f:\n"
           "        f.write(blob)\n")
    kinds = [f.kind for f in lint_source(src, "authorino_tpu/x.py")]
    assert kinds == ["non-atomic-write"]
    # the full discipline in the same scope passes: fsync + os.replace
    ok = ("import os\n"
          "def dump(snapshot_path, blob):\n"
          "    with open(snapshot_path + '.tmp', 'wb') as f:\n"
          "        f.write(blob)\n"
          "        f.flush()\n"
          "        os.fsync(f.fileno())\n"
          "    os.replace(snapshot_path + '.tmp', snapshot_path)\n")
    assert lint_source(ok, "authorino_tpu/x.py") == []
    # str.replace is NOT os.replace: the finding survives
    bad = ("import os\n"
           "def dump(snapshot_path, blob):\n"
           "    with open(snapshot_path, 'wb') as f:\n"
           "        f.write(blob)\n"
           "        os.fsync(f.fileno())\n"
           "    snapshot_path.replace('.tmp', '')\n")
    assert [f.kind for f in lint_source(bad, "authorino_tpu/x.py")] \
        == ["non-atomic-write"]
    # non-durable paths (no durable-artifact word in scope) are exempt —
    # this lint hunts restart-critical state, not every scratch file
    scratch = ("def dump(p, blob):\n"
               "    with open(p, 'wb') as f:\n"
               "        f.write(blob)\n")
    assert lint_source(scratch, "authorino_tpu/x.py") == []
    # reads never fire, tests/ are exempt, suppression is reasoned
    assert lint_source("def load(manifest_path):\n"
                       "    return open(manifest_path).read()\n",
                       "authorino_tpu/x.py") == []
    assert lint_source(src, "tests/test_x.py") == []
    ok2 = ("def dump(snapshot_path, blob):\n"
           "    with open(snapshot_path, 'wb') as f:"
           "  # lint-ok: non-atomic-write -- sentinel file\n"
           "        f.write(blob)\n")
    assert lint_source(ok2, "authorino_tpu/x.py") == []


def test_repo_stays_lint_clean():
    """The tier-1 gate: the new code lint over authorino_tpu/ must report
    no findings — a new blocking call in an async path, a lock held across
    await, a tracer branch in a jitted fn, or a bare except FAILS CI until
    fixed or suppressed with a reasoned `# lint-ok: <kind>` comment."""
    import authorino_tpu

    root = authorino_tpu.__path__[0]
    findings = lint_paths([root])
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# --strict-verify: swap rejection keeps the old snapshot serving
# ---------------------------------------------------------------------------


def _entries(configs):
    return [EngineEntry(id=c.name, hosts=[f"{c.name}.example.com"],
                        runtime=None, rules=c) for c in configs]


def test_strict_verify_rejects_corrupt_swap(monkeypatch):
    from authorino_tpu.runtime import engine as engine_mod
    from authorino_tpu.utils import metrics as metrics_mod

    eng = PolicyEngine(mesh=None, strict_verify=True, analyze_policies=False)
    # this test simulates a COMPILER bug by monkeypatching compile_corpus:
    # the incremental compile cache (ISSUE 8) would honestly skip the
    # recompile of an identical corpus, so force the monolithic path
    eng.compile_cache = None
    eng.apply_snapshot(_entries(fixture_configs()))
    g1 = eng.generation
    snap1 = eng._snapshot
    assert g1 == 1 and snap1 is not None

    real = engine_mod.compile_corpus

    def corrupt(*a, **k):
        p = real(*a, **k)
        p.dfa_table_of_row = p.dfa_table_of_row.copy()
        p.dfa_table_of_row[0] = p.dfa_tables.shape[0] + 7
        return p

    monkeypatch.setattr(engine_mod, "compile_corpus", corrupt)
    with pytest.raises(SnapshotRejected) as ei:
        eng.apply_snapshot(_entries(fixture_configs()))
    assert {f.kind for f in ei.value.findings} == {"dfa-table-index"}
    # the OLD snapshot is still live: generation unbumped, index serving
    assert eng.generation == g1
    assert eng._snapshot is snap1
    assert eng.lookup("api.example.com") is not None
    # and the rejection is counted (noop-metrics images skip the read)
    try:
        from prometheus_client import REGISTRY

        v = REGISTRY.get_sample_value(
            "auth_server_snapshot_rejected_total", {"component": "engine"})
        assert v is not None and v >= 1
    except ImportError:
        pass

    # a clean corpus swaps again afterwards
    monkeypatch.setattr(engine_mod, "compile_corpus", real)
    eng.apply_snapshot(_entries(fixture_configs()))
    assert eng.generation == g1 + 1


def test_strict_verify_off_by_default():
    eng = PolicyEngine(mesh=None)
    assert eng.strict_verify is False
    eng.apply_snapshot(_entries(fixture_configs()))
    assert eng.generation == 1
    # unvetted snapshots are NOT marked lint_ok: a strict native frontend
    # must lint them itself at refresh time
    assert eng._snapshot.lint_ok is False


def test_strict_verify_marks_snapshot_vetted():
    # the native frontend's refresh skips re-linting snapshots the engine
    # already vetted (runtime/native_frontend.py _refresh_locked)
    eng = PolicyEngine(mesh=None, strict_verify=True, analyze_policies=False)
    eng.apply_snapshot(_entries(fixture_configs()))
    assert eng._snapshot.lint_ok is True


# ---------------------------------------------------------------------------
# reconcile-path analysis: once per swap, on /debug/vars, metrics counted
# ---------------------------------------------------------------------------


def test_engine_analysis_on_debug_vars(caplog):
    import logging

    eng = PolicyEngine(mesh=None)
    entries = _entries(fixture_configs() + finding_fixture_configs())
    entries[1].hosts.append("api.example.com")  # planted duplicate host
    with caplog.at_level(logging.WARNING, logger="authorino_tpu.engine"):
        eng.apply_snapshot(entries)
    pa = eng.debug_vars()["policy_analysis"]
    assert pa is not None and pa["generation"] == 1
    kinds = {f["kind"] for f in pa["findings"]}
    assert {"duplicate-host", "constant-allow", "constant-deny",
            "shadowed-rule", "duplicate-rule"} <= kinds
    # logged exactly once per reconcile, not per finding/request
    msgs = [r for r in caplog.records if "policy analysis" in r.message]
    assert len(msgs) == 1


def test_engine_analysis_never_breaks_reconcile(monkeypatch):
    from authorino_tpu.runtime import engine as engine_mod

    eng = PolicyEngine(mesh=None)

    def boom(*a, **k):
        raise RuntimeError("analyzer bug")

    monkeypatch.setattr(
        "authorino_tpu.analysis.policy_analysis.analyze_snapshot", boom)
    eng.apply_snapshot(_entries(fixture_configs()))  # must not raise
    assert eng.generation == 1
    assert eng.debug_vars()["policy_analysis"] is None


# ---------------------------------------------------------------------------
# CLI: python -m authorino_tpu.analysis
# ---------------------------------------------------------------------------


def test_cli_self_lint_json(capsys):
    from authorino_tpu.analysis.__main__ import main

    assert main(["--self-lint", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] is True and report["findings"] == []


def test_cli_verify_fixtures(capsys):
    from authorino_tpu.analysis.__main__ import main

    assert main(["--verify-fixtures"]) == 0
    assert "OK" in capsys.readouterr().out


def test_cli_reports_findings(tmp_path, capsys):
    from authorino_tpu.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    assert main(["--self-lint", str(bad)]) == 1
    assert "blocking-in-async" in capsys.readouterr().out
