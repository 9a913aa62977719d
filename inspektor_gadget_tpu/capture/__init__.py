"""Capture/replay plane: durable segmented event journals, a
deterministic replay source, and the cluster-wide recording lifecycle.

The live pipeline is live-or-lost once a batch leaves the operator
chain; this package closes the gap the way production trace tooling
does — record the typed stream durably (journal.py), manage node-wide
recordings (manager.py, armed by the capture operator riding every run),
and re-drive any journal through the real operator chain on an
injectable clock (replay.py) so a bug seen on a node replays on a
laptop, the bench harness gets reproducible input, and `alerts test`
dry-runs rules against real recorded traffic.
"""

from .journal import (
    JOURNAL_SCHEMA,
    JournalReader,
    JournalWriter,
    SegmentLoss,
    build_manifest,
    capture_base_dir,
    is_journal,
    summary_digest,
    summary_to_dict,
)
from .manager import RECORDINGS, Recording, RecordingManager
from .replay import (
    ReplayClock,
    ReplayResult,
    iter_journals,
    replay_journal,
)

__all__ = [
    "JOURNAL_SCHEMA", "JournalReader", "JournalWriter", "RECORDINGS",
    "Recording", "RecordingManager", "ReplayClock", "ReplayResult",
    "SegmentLoss", "build_manifest", "capture_base_dir", "is_journal",
    "iter_journals", "replay_journal", "summary_digest", "summary_to_dict",
]
