"""Traffic replay & what-if preflight (ISSUE 13, docs/replay.md).

Three layers composing PR 8 (loadable snapshots), PR 9 (decision/attribution
provenance) and PR 10 (canary guards) into "test a policy change against
yesterday's traffic with zero live exposure":

- :mod:`.capture`   — opt-in byte-bounded sampled request log (in-memory
  ring + checksummed on-disk segments), fed off the hot path;
- :mod:`.replay`    — offline verdict-diff: re-decide captured requests
  against two snapshots through the exact host oracle, flips grouped by
  (authconfig, rule) via provenance attribution;
- :mod:`.pregate`   — the reconcile preflight gate: a diff breaching the
  canary guard thresholds rejects the swap BEFORE the canary window.

Only the import-light capture surface is re-exported here; the replay /
pregate layers import the host oracle (and with it jax) — pull them in
explicitly: ``from authorino_tpu.replay.replay import replay_records``.
"""

from .capture import (  # noqa: F401
    CAPTURE,
    CAPTURE_SCHEMA,
    CaptureFormatError,
    CaptureLog,
    read_capture,
    read_segment,
    write_segment,
)

__all__ = ["CAPTURE", "CAPTURE_SCHEMA", "CaptureFormatError", "CaptureLog",
           "read_capture", "read_segment", "write_segment"]
