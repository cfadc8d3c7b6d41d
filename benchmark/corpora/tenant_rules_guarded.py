"""Corpus `tenant_rules_guarded`: `tenant_rules`' manifests and rows as they
are, for a size at which a program with dense operands cannot boot on the
one-chip machine, behind one question asked of the checkout before the
server child is started.

Why it exists (PR 28).  At 10,000 AuthConfigs a program whose served operands
are the G x L one-hots builds ~25 GB of f32 numpy, their bf16 copies and a
host view: its resident set passes 35 GB 151 s after its start and 50 GB at
184 s (PR 27's and PR 28's chip runs of the commit before PR 28), the
one-chip machine has 40 GiB, and the child is killed there.  A run that is
killed for its memory proves nothing and, in the driver's check, refuses
the PR that brought the cell.  So the run is refused here, at once and with
the reason, which is a clean failure of that side.

The question.  Such a program cannot be told by its size before it has
grown, so it is told by what it says of itself: the program whose served
entry evaluates a row's own config publishes `/debug/vars`
`native_frontend.snapshot.kernel.leaf_cols_per_row` (the cell's metric of
that name reads it), and the dense program has no such counter.  The
counter's name is looked for in the package's sources; nothing is imported,
so this process stays free of jax.  It is a stand-in for a limit on the
child's memory, which belongs in harness.py (PERF.md section 7).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
COUNTER = "leaf_cols_per_row"

_spec = importlib.util.spec_from_file_location(
    "bench_corpora_tenant_rules", os.path.join(HERE, "tenant_rules.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

requests = _base.requests


def publishes_counter(root: str, counter: str = COUNTER) -> bool:
    """Whether any source of the package under `root` names the counter."""
    for base, _, names in os.walk(os.path.join(root, "authorino_tpu")):
        for name in names:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(base, name), errors="replace") as f:
                if counter in f.read():
                    return True
    return False


def manifests(params: Dict[str, Any], root: str = ROOT) -> List[Dict[str, Any]]:
    if not publishes_counter(root):
        from harness import Refused

        raise Refused(
            f"the program under {root} does not publish {COUNTER}: its served "
            f"operands are dense, and at {int(params['n_configs'])} AuthConfigs "
            "it is killed for its memory before it is ready "
            "(benchmark/corpora/tenant_rules_guarded.py)")
    return _base.manifests(params)
