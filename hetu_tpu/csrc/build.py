"""Build the native components (C++17, no external deps) into shared libs.

Replaces the reference's cmake build (CMakeLists.txt, cmake/config.example.cmake)
with a dependency-free g++ invocation. A library is keyed on a hash of its
sources, headers and compiler line, carried in its file name: a library that
does not match the sources on disk — a stale one copied along with a checkout,
whatever its mtime — is never loaded, and `import hetu_tpu.ps` always works
after a checkout (mirroring how the reference loads prebuilt .so files in
_base.py:78-90).
"""
from __future__ import annotations

import contextlib
import glob
import hashlib
import os
import subprocess
import sys

_CSRC = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_CSRC, "build")
_CXX = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread"]

# One library: the cache shares the PS worker agent's process globals
# (the reference links hetu_cache against ps-lite the same way).
_TARGETS = {
    "libhetu_ps.so": {
        "srcs": ["ps/capi.cc", "cache/cache_capi.cc"],
        "deps": ["ps/net.h", "ps/store.h", "ps/server.h", "ps/scheduler.h",
                 "ps/worker.h", "ps/ring.h", "ps/chaos.h", "cache/cache.h"],
    },
}


def _content_key(paths) -> str:
    h = hashlib.sha256(" ".join(_CXX).encode())
    for p in paths:
        h.update(os.path.relpath(p, _CSRC).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def build(name: str) -> str:
    """Build (unless a library for exactly these sources exists) and return
    the path to the named shared library."""
    spec = _TARGETS[name]
    srcs = [os.path.join(_CSRC, s) for s in spec["srcs"]]
    deps = srcs + [os.path.join(_CSRC, d) for d in spec["deps"]]
    missing = [p for p in deps if not os.path.exists(p)]
    if missing:
        raise FileNotFoundError(f"cannot build {name}: missing {missing}")
    stem, ext = os.path.splitext(name)
    out = os.path.join(_BUILD, f"{stem}.{_content_key(deps)}{ext}")
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD, exist_ok=True)
    # several workers of one job may build at once: each writes its own
    # file and the rename publishes a complete library atomically
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = _CXX + ["-I", _CSRC, "-o", tmp] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        sys.stderr.write(e.stderr)
        raise RuntimeError(f"native build of {name} failed: {' '.join(cmd)}")
    os.replace(tmp, out)
    for stale in glob.glob(os.path.join(_BUILD, f"{stem}*{ext}")):
        if stale != out:
            with contextlib.suppress(FileNotFoundError):  # a racing builder
                os.remove(stale)
    return out
