"""Durable atomic file writes: stage → fsync → rename → directory fsync.

The invariant every consumer gets: the destination path always holds either
the **previous** good version or the **new** good version, never a partial
or torn file — under process crash (kill -9 at any instruction) and, with
``fsync``, under OS crash/power loss once the rename is durable.

The recipe (the classic POSIX sequence):

1. write the full content to a staging file ``<name>.<pid>.<seq>.tmp`` in
   the **same directory** (same filesystem, so the rename is atomic),
2. ``flush`` + ``os.fsync`` the staging file (data hits the device before
   the rename can make it visible),
3. ``os.replace`` onto the destination (atomic on POSIX and Windows),
4. ``fsync`` the directory on POSIX so the rename itself is durable.

Crash points (``reliability.faults.maybe_crash``) are threaded between the
stages so the crash-matrix tests can kill the process at every boundary:
``durable.staged`` / ``durable.synced`` / ``durable.replaced``.
"""
from __future__ import annotations

import contextlib
import itertools
import os
from pathlib import Path

from .. import obs
from . import faults

_seq = itertools.count()


@obs.span("durable.fsync_dir")
def fsync_dir(path: str | Path) -> None:
    """fsync a directory so a rename inside it survives OS crash.  No-op on
    platforms whose directories cannot be opened (e.g. Windows) — there
    ``os.replace`` is already as durable as the platform offers."""
    try:
        fd = os.open(str(path), os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class DurableFile:
    """A staged file with explicit commit/discard — the streaming face of
    :func:`durable_write` (for writers that emit bytes incrementally and
    decide success only at the end, e.g. ``ContainerWriter``).

    ``.file`` is the staging handle (same directory as the target).
    ``commit()`` runs fsync → replace → dir-fsync; ``discard()`` closes and
    unlinks the stage, leaving any previous destination untouched.
    """

    def __init__(self, path: str | Path, fsync: bool = True):
        self.path = Path(path)
        self.stage = self.path.with_name(
            f"{self.path.name}.{os.getpid()}.{next(_seq)}.tmp"
        )
        self.fsync = fsync
        self.file = open(self.stage, "wb")
        self._done = False

    def commit(self) -> None:
        if self._done:
            return
        self.file.flush()
        faults.maybe_crash("durable.staged")
        if self.fsync:
            with obs.span("durable.fsync"):
                os.fsync(self.file.fileno())
        self.file.close()
        faults.maybe_crash("durable.synced")
        with obs.span("durable.replace"):
            os.replace(self.stage, self.path)
        faults.maybe_crash("durable.replaced")
        if self.fsync:
            fsync_dir(self.path.parent)
        self._done = True

    def discard(self) -> None:
        """Abandon the write: the destination keeps its previous content."""
        if self._done:
            return
        self._done = True
        with contextlib.suppress(OSError):
            self.file.close()
        with contextlib.suppress(OSError):
            os.unlink(self.stage)


@contextlib.contextmanager
def durable_write(path: str | Path, fsync: bool = True):
    """Context manager yielding a staging file handle; commits atomically on
    clean exit, discards (previous version untouched) on exception::

        with durable_write(p) as f:
            f.write(header)
            f.write(body)
        # p now holds exactly header+body, or its previous content if the
        # block raised / the process died
    """
    df = DurableFile(path, fsync=fsync)
    try:
        yield df.file
    except BaseException:
        df.discard()
        raise
    df.commit()


def write_bytes(path: str | Path, data: bytes, fsync: bool = True) -> None:
    """One-shot durable replacement of ``path`` with ``data``."""
    with durable_write(path, fsync=fsync) as f:
        f.write(data)


def replace_dir(stage: str | Path, dest: str | Path,
                fsync: bool = True) -> None:
    """Atomically promote a fully-staged directory onto ``dest`` (which must
    not exist — callers that overwrite move the old version aside first).
    fsyncs the parent so the rename is durable."""
    stage, dest = Path(stage), Path(dest)
    faults.maybe_crash("checkpoint.staged")
    os.replace(stage, dest)
    faults.maybe_crash("checkpoint.committed")
    if fsync:
        fsync_dir(dest.parent)
