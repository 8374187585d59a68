"""The JSONL sink of structured records (SURVEY.md §6 "Metrics / logging").

The reference logs periodic throughput lines from workers; here soak, the
train CLIs, the cluster roles and the drills write events and registry
snapshots as one JSON object a line.
"""

from __future__ import annotations

import io
import json
import time
from typing import Any, TextIO


class MetricsLogger:
    """Append-only JSONL sink; file path, open stream, or in-memory."""

    def __init__(self, sink: str | TextIO | None = None) -> None:
        self._own = False
        self._final: str | None = None  # StringIO contents cached at close
        if sink is None:
            self._stream: TextIO = io.StringIO()
        elif isinstance(sink, str):
            self._stream = open(sink, "a", buffering=1)
            self._own = True
        else:
            self._stream = sink

    def log_event(self, **fields: Any) -> None:
        fields.setdefault("t", time.time())
        self._stream.write(json.dumps(fields) + "\n")

    def log_snapshot(self, registry, **extra: Any) -> None:
        """One ``metrics_snapshot`` record carrying a whole
        ``obs.metrics.Registry`` — how existing JSONL consumers (soak, the
        training CLIs) get the registry stream without learning a new sink."""
        self.log_event(
            kind="metrics_snapshot", metrics=registry.snapshot(), **extra
        )

    def close(self) -> None:
        """Flush buffered writes on EVERY sink — a caller-owned stream is
        flushed (not closed: its lifetime is the caller's), an owned file
        is flushed and closed, and an in-memory sink's contents stay
        readable via ``dump()`` even if someone closes the StringIO."""
        if isinstance(self._stream, io.StringIO):
            try:
                self._final = self._stream.getvalue()
            except ValueError:  # owner closed it first: keep what we have
                pass
            return
        try:
            self._stream.flush()
        except ValueError:  # already closed by its owner: nothing buffered
            pass
        if self._own:
            self._stream.close()

    def dump(self) -> str:
        if self._final is not None:
            return self._final
        if isinstance(self._stream, io.StringIO):
            try:
                return self._stream.getvalue()
            except ValueError:
                return ""
        return ""
