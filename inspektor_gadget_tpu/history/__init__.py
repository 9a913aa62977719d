"""Sketch history plane: time-windowed sketch store, fleet-wide range
queries, and subpopulation slices.

Live harvests render and vanish; checkpoints exist only for resume.
This package makes sketch state queryable across time and space
(arxiv 2503.13515, 2208.04927): the tpusketch operator seals one
mergeable window per boundary into a per-node store built on the PR-5
journal disciplines (window.py + store.py), agents serve
ListWindows/FetchWindows, and the query plane (query.py) merges
index-overlapping windows client-side — `ig-tpu query` answers
"cardinality of tenant X, 2–3pm, across nodes" from sealed state.
"""

from .archive import (
    ARCHIVE_MANIFEST,
    ARCHIVE_SCHEMA,
    ArchiveBackend,
    ArchiveTier,
    FilesystemArchive,
)
from .lifecycle import (
    DEFAULT_SCHEDULE,
    CompactionEngine,
    ScheduleLevel,
    parse_schedule,
    validate_schedule,
)
from .query import (
    QueryAnswer,
    answer_query,
    decode_frames,
    dedupe_compacted,
    level_counts,
    pack_frames,
    unpack_frames,
)
from .store import (
    HISTORY,
    HISTORY_METRICS,
    HISTORY_SCHEMA,
    HistoryStore,
    history_base_dir,
    validate_store_name,
)
from .window import (
    MergedWindows,
    SealedWindow,
    SliceSketch,
    WINDOW_SCHEMA,
    WindowSlices,
    decode_window,
    encode_window,
    header_overlaps,
    merge_windows,
    merged_to_sealed,
    provenance_row,
    window_digest,
)

__all__ = [
    "ARCHIVE_MANIFEST", "ARCHIVE_SCHEMA", "ArchiveBackend", "ArchiveTier",
    "CompactionEngine", "DEFAULT_SCHEDULE", "FilesystemArchive", "HISTORY",
    "HISTORY_METRICS", "HISTORY_SCHEMA", "HistoryStore", "MergedWindows",
    "QueryAnswer", "ScheduleLevel", "SealedWindow", "SliceSketch",
    "WINDOW_SCHEMA", "WindowSlices", "answer_query", "decode_frames",
    "decode_window", "dedupe_compacted", "encode_window", "header_overlaps",
    "history_base_dir", "level_counts", "merge_windows", "merged_to_sealed",
    "pack_frames", "parse_schedule", "provenance_row", "unpack_frames",
    "validate_schedule", "validate_store_name", "window_digest",
]
