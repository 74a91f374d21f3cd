"""In-tree ``g++ -shared`` build keyed by content.

The binaries are not tracked by git: a checkout holds sources only and
each loader builds on first use.  A binary is reused only when the stamp
beside it (``<so>.sha256``) matches the hash of the exact source bytes
and command line it was built from — never by mtime, which a copied
tree does not preserve.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Sequence

__all__ = ["build_shared", "failure_text"]


def failure_text(e: BaseException) -> str:
    """The exception plus the end of the compiler's stderr, if any."""
    err = getattr(e, "stderr", None)
    return f"{e!r}: {err[-400:]}" if err else repr(e)


def build_shared(so: str, sources: Sequence[str],
                 args: Sequence[str] = ()) -> None:
    """Compile ``sources[0]`` (the rest are headers it includes) into
    ``so`` unless ``so`` was already built from these bytes with this
    command.  Raises ``OSError`` (no compiler) or
    ``subprocess.CalledProcessError`` (stderr attached) on failure."""
    tmp = so + f".tmp.{os.getpid()}"
    # libraries in ``args`` must follow the source that needs them
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", sources[0],
           "-o", tmp, *args]
    h = hashlib.sha256(" ".join(c for c in cmd if c != tmp).encode())
    for path in sources:
        with open(path, "rb") as f:
            h.update(f.read())
    key, stamp = h.hexdigest(), so + ".sha256"
    try:
        with open(stamp) as f:
            if os.path.exists(so) and f.read().strip() == key:
                return
    except OSError:
        pass
    subprocess.run(cmd, check=True, capture_output=True, text=True,
                   timeout=240)
    os.replace(tmp, so)
    with open(stamp, "w") as f:
        f.write(key)
