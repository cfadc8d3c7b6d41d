"""chip_smoke.py on the CPU (tier-1): the generator and oracle are
deterministic, the command line refuses anything but a TPU, and the same
driver functions — given the expected platform as an argument — pass end
to end against a CPU server at tiny size and refuse a server whose device
path is not what served."""

import copy
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402


def test_generator_and_oracle_are_deterministic_under_seed():
    corpus = cs.make_corpus(8)
    a = cs.make_requests(7, 8, 64, 4)
    b = cs.make_requests(7, 8, 64, 4)
    assert a == b
    want = cs.oracle_verdicts(corpus, a)
    assert want == cs.oracle_verdicts(cs.make_corpus(8), b)
    assert cs.table_digest(a, want) == cs.table_digest(b, want)
    assert cs.make_requests(8, 8, 64, 4) != a
    # the table exercises what it claims: both verdicts, both transports,
    # per-config regexes and membership leaves in every config
    assert 0 < sum(want) < len(want)
    assert [r["transport"] for r in a].count("http") == 4
    for manifest in corpus:
        ops = [p["operator"] for p in manifest["spec"]["authorization"][
            "rules"]["patternMatching"]["patterns"]]
        assert len(ops) == 10
        assert ops.count("matches") >= 2 and "incl" in ops
    regexes = {p["value"] for i in range(8) for p in cs.config_patterns(i)
               if p["operator"] == "matches"}
    assert len(regexes) == 16  # two per config, all distinct


def test_each_violation_breaks_exactly_its_rule():
    """The oracle, not the generator's intent, decides: every one of the
    ten ways to break a request flips the oracle's verdict to deny."""
    import random

    corpus = cs.make_corpus(3)
    rng = random.Random(1)
    for i in range(3):
        for key, breaker in cs._VIOLATIONS:
            vals = cs._allowed_values(i, rng)
            ok = dict(vals)
            vals[key] = breaker(i, vals)

            def row(v):
                v = dict(v)
                return {"transport": "grpc", "config": i,
                        "host": cs.host_of(i), "method": v.pop("method"),
                        "path": v.pop("path"), "headers": v}

            assert cs.oracle_verdicts(corpus, [row(ok), row(vals)]) == \
                [True, False], (i, key)


def test_smoke_passes_end_to_end_on_cpu_when_told_to_expect_one(tmp_path):
    # lane selection off: on a CPU the host twin and the "device" are the
    # same silicon, so the cost model's choice between them is a coin toss
    # the 90 % device-share rule would ride on
    summary, why = cs.run_smoke(
        str(tmp_path), seed=3, expected_platform="cpu", n_configs=8,
        n_grpc=384, n_http=4, server_args=("--no-lane-select",),
        ready_timeout_s=300)
    assert why == [], why
    assert summary["platform"] == "cpu" and summary["device_count"] >= 1
    assert summary["mismatches"] == []
    # conftest's environment reaches the child: 8 virtual CPU devices, so
    # the default mesh="auto" shards the corpus and the native lane serves
    # through the shard_map step — with launches on every device
    assert summary["device_count"] == 8
    assert summary["corpus"]["authconfigs"] == 8
    assert summary["corpus"]["fast_configs"] == 8
    assert summary["corpus"]["sharded"] is True
    assert all(summary["mesh"]["launches"].values())
    assert summary["native_frontend"]["stats"]["fast"] == 384
    assert summary["native_frontend"]["source_digest"] == \
        summary["native_source_digest"]
    # two configs a shard, two regexes a config: the mesh step evaluates
    # every leaf and scans every DFA row of its shard for every request row
    # (the dense body)
    kernel = dict(summary["kernel"])
    assert kernel.pop("operand_bytes") > 0
    assert kernel == {"lane": "matmul",
                      "entry": "sharded_step", "leaf_cols_per_row": 32,
                      "dfa_rows_per_row": 4, "dfa_rows_total": 4,
                      "dfa_states": kernel["dfa_states"], "dfa_cpu_leaves": 0}
    assert kernel["dfa_states"] % 8 == 0 < kernel["dfa_states"]
    assert summary["wire_device_rows"] >= 0.9 * 384
    assert summary["warm_grid"] and summary["exit_code"] == 0
    assert summary["compile_cache"]["dir"]

    # the same evidence, doctored: each way the device path can fail to be
    # what served is a reason of its own
    def refused(**patch):
        s = copy.deepcopy(summary)
        s.update(patch)
        return cs.judge(s, "cpu", 384, 8)

    assert refused() == []
    assert any("platform" in w for w in cs.judge(summary, "tpu", 384, 8))
    assert any("mismatch" in w for w in refused(mismatches=[{"row": 0}]))
    assert any("exit code" in w for w in refused(exit_code=1))
    assert any("exit code" in w for w in refused(exit_code=None))
    assert any("not built from" in w
               for w in refused(native_source_digest="0" * 64))
    assert any("device launch" in w for w in refused(wire_device_rows=100))
    assert any("miss" in w for w in refused(jit_warm_miss_after_ready=1.0))
    assert any("breaker" in w
               for w in refused(breakers={"engine": "closed",
                                          "native": "open"}))
    assert any("server log" in w
               for w in refused(log_findings=["jit pre-warm failed"]))
    assert any("not sharded" in w for w in refused(mesh=None))
    idle = dict(summary["mesh"], launches={"0": 5, "1": 0})
    assert any("launches is zero on ['1']" in w for w in refused(mesh=idle))
    starved = dict(summary["mesh"], upload_bytes_by_shard={"0": 9.0})
    assert any("upload bytes are zero" in w for w in refused(mesh=starved))


def test_smoke_refuses_a_server_that_degrades(tmp_path, monkeypatch):
    """device-down armed through the normal --fault-profile flag: every
    verdict is still exact (the degrade path answers), and that is exactly
    why the smoke must look at what served, not at the answers."""
    # one device for this child (conftest's XLA_FLAGS would shard it): the
    # native lane's exact CPU-twin degrade exists for a single corpus only
    monkeypatch.delenv("XLA_FLAGS")
    summary, why = cs.run_smoke(
        str(tmp_path), seed=3, expected_platform="cpu", n_configs=8,
        n_grpc=128, n_http=4,
        server_args=("--no-lane-select", "--fault-profile", "device-down"),
        ready_timeout_s=300)
    assert summary["mismatches"] == []
    assert summary["failure_counters"][
        "auth_server_degraded_decisions_total"] > 0
    assert any("degraded_decisions_total" in w for w in why), why
    assert any("device launch" in w for w in why), why


def test_command_line_refuses_a_cpu(tmp_path):
    """`python chip_smoke.py` has no argument, environment variable or
    default that accepts a CPU: here it must exit non-zero, say why, and
    print no result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=280, cwd=str(tmp_path))
    assert p.returncode == 1, p.stderr[-2000:]
    assert "REFUSED: platform is 'cpu', not 'tpu'" in p.stderr
    assert p.stdout.strip() == ""
    # refused before serving: nothing was sent
    diag = json.loads(next(line for line in p.stderr.splitlines()
                           if line.startswith("{")))
    assert "mismatches" not in diag and diag["claim"] is None
