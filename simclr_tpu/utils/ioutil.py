"""Crash-safe file writes shared by the entry points.

Every artifact a resume gate later trusts (eval's ``results.json``,
save_features' ``.npy`` exports) must hit the filesystem atomically: a
SIGKILL mid-write must leave either the old file or the new one, never a
truncated hybrid that an existence check would carry forward as complete.
"""

from __future__ import annotations

import os
import tempfile
from typing import Callable, IO

# probed once at import: os.umask(0)+restore is a process-global race — a
# thread opening files between the two calls would briefly create
# world-writable artifacts (eval and save_features both write from worker
# threads)
_UMASK = os.umask(0)
os.umask(_UMASK)


def atomic_append(path: str, text: str) -> None:
    """Append ``text`` to ``path`` as ONE ``write(2)`` on an ``O_APPEND`` fd.

    POSIX makes the seek-to-end and the write atomic together, so concurrent
    appenders (the training child and the supervisor runner both write
    ``events.jsonl``) interleave whole records, never torn ones — provided
    each record is a single write, which is why this takes the full string
    rather than a file object. No fsync: timeline events are forensics, not
    resume gates (same trade as ``supervisor/heartbeat.py``).
    """
    data = text.encode()
    fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o666 & ~_UMASK)
    try:
        os.write(fd, data)
    finally:
        os.close(fd)


def atomic_write(path: str, write_fn: Callable[[IO], None], mode: str = "w") -> None:
    """Write via ``write_fn(file)`` to a unique temp file, then rename.

    ``mode`` is ``"w"`` for text (json.dump) or ``"wb"`` for binary
    (np.save). The rename is atomic on POSIX; the tmp file lives in the
    destination directory so the replace never crosses filesystems. The
    tmp name is unique per call (ADVICE r4: a fixed ``path + ".tmp"``
    lets two concurrent writers corrupt the winner — writer A's open fd
    keeps writing into the inode writer B renamed into place), and the
    data is fsynced before the rename so a power loss cannot surface an
    empty file under the final name.
    """
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path) or ".", prefix=os.path.basename(path) + ".tmp."
    )
    try:
        # mkstemp creates 0600; restore umask-governed permissions so shared
        # artifacts (results JSON, feature exports) stay readable as before
        os.fchmod(fd, 0o666 & ~_UMASK)
        with os.fdopen(fd, mode) as f:
            write_fn(f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
