"""Build the native components on demand (g++ → .so, cached by content).

The reference ships prebuilt native artifacts via Bazel (BUILD.bazel →
_raylet.so, raylet, gcs_server); here the native library is compiled once at
first import and cached under _native/build/. The artifact's name carries a
hash of its sources and compile command, so a stale .so is never loaded:
build/ is ignored by git but travels with a copied tree, and a copy does
not keep modification times in order.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "build")
_LOCK = threading.Lock()
_CXX = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-Wall"]

_LIBS = {
    "ray_tpu_store": ["shm_store.cpp"],
    "ray_tpu_transfer": ["shm_store.cpp", "transfer.cpp"],
    "ray_tpu_channel": ["mutable_channel.cpp"],
    "ray_tpu_fastlane": ["fastlane.cpp"],
}


def lib_path(name: str) -> str:
    """Where lib<name> built from the sources as they are now lives."""
    digest = hashlib.sha256(" ".join(_CXX).encode())
    for src in _LIBS[name]:
        with open(os.path.join(_DIR, src), "rb") as f:
            digest.update(src.encode() + b"\0" + f.read())
    return os.path.join(_BUILD_DIR,
                        f"lib{name}.{digest.hexdigest()[:16]}.so")


def ensure_built(name: str, force: bool = False) -> str:
    """Compile lib<name>.so unless this exact build exists; return its
    path."""
    out = lib_path(name)
    with _LOCK:
        if not force and os.path.exists(out):
            return out
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = f"{out}.{os.getpid()}.tmp"  # workers may build concurrently
        sources = [os.path.join(_DIR, s) for s in _LIBS[name]]
        subprocess.run(_CXX + ["-o", tmp] + sources + ["-lpthread"],
                       check=True, capture_output=True, text=True)
        os.replace(tmp, out)
        for old in glob.glob(os.path.join(_BUILD_DIR, f"lib{name}.*so")):
            if old != out:
                try:
                    os.remove(old)
                except FileNotFoundError:
                    pass  # another process swept it first
    return out


def load_lib(name: str):
    """ensure_built + ctypes.CDLL, recompiling once if the cached .so fails
    to load (e.g. an artifact built on a different platform/glibc)."""
    import ctypes

    try:
        return ctypes.CDLL(ensure_built(name))
    except OSError:
        return ctypes.CDLL(ensure_built(name, force=True))
