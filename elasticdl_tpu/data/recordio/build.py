"""Build the native EDLIO codec: ``python -m elasticdl_tpu.data.recordio.build``.

Compiles ``_native.cc`` into ``_native.so`` next to this file.  ``*.so``
is git-ignored, so a fresh checkout has none: training entry points call
``recordio.ensure_native_codec()``, which builds it here.  The library
carries the digest of the source it was built from; one built from other
source is stale (decided by CONTENT — a copied checkout does not
preserve mtimes) and is rebuilt, never loaded.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(_HERE, "_native.cc")
OUTPUT = os.path.join(_HERE, "_native.so")

# the library embeds this marker followed by the hex digest of its source
_DIGEST_MARKER = "EDLIO_SOURCE_SHA256="


def source_digest() -> str:
    with open(SOURCE, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def is_current() -> bool:
    """``_native.so`` exists and was built from today's ``_native.cc``."""
    try:
        with open(OUTPUT, "rb") as f:
            built = f.read()
    except OSError:
        return False
    return (_DIGEST_MARKER + source_digest()).encode() in built


def build(force: bool = False, quiet: bool = False) -> str | None:
    """Compile the codec; returns the .so path or None on failure."""
    if not force and is_current():
        return OUTPUT
    # compile beside the target and rename into place: concurrent
    # builders (several workers on a fresh checkout) each install a whole
    # library, and a loader never maps a half-written one
    staging = f"{OUTPUT}.{os.getpid()}.tmp"
    cmd = [
        "g++",
        "-O2",
        "-std=c++17",
        "-shared",
        "-fPIC",
        f'-DEDLIO_SOURCE_DIGEST="{_DIGEST_MARKER}{source_digest()}"',
        SOURCE,
        "-lz",
        "-o",
        staging,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=quiet)
        os.replace(staging, OUTPUT)
    except (subprocess.CalledProcessError, OSError) as e:
        if not quiet:
            print(f"EDLIO native build failed: {e}", file=sys.stderr)
        return None
    finally:
        if os.path.exists(staging):
            os.remove(staging)
    return OUTPUT


if __name__ == "__main__":
    path = build(force="--force" in sys.argv)
    if path is None:
        sys.exit(1)
    print(path)
