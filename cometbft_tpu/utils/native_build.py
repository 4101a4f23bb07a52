"""Shared build-on-demand loader for the in-tree C++ components.

One implementation of the pattern crypto/bls_native.py and
utils/kv_native.py previously each carried: compile the single-file
source with g++ when no matching artefact exists, load via ctypes,
and report — never hide — when the toolchain or library is
unavailable.

The artefact is keyed by CONTENT: its file name carries a digest of
the source text, the compiler flags and this host's CPU feature
flags (the build uses ``-march=native``).  A library built from
another tree, from an older source, or on another CPU has a different
name and is never loaded — a checkout copied to another machine
rebuilds there.  The temp output is pid-unique so concurrent builders
(parallel test workers on a clean checkout) cannot replace each
other's half-written object.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# -O3 + native tuning: these libs are built ON the box they run on
# (never shipped), and the BLS pairing is pure bigint arithmetic where
# vectorized/unrolled codegen is measurably faster than -O2
_CXXFLAGS = (
    "-O3", "-march=native", "-funroll-loops", "-shared", "-fPIC",
    "-std=c++17",
)


def _cpu_identity() -> bytes:
    """What ``-march=native`` resolves against: the machine type plus
    the first CPU's feature flags (Linux /proc/cpuinfo)."""
    ident = platform.machine()
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    ident += line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return ident.encode()


class NativeLib:
    """Lazily built + loaded shared library handle.

    ``status`` says what happened, for surfaces that must not pass a
    pure-Python fallback off as the native path: ``unloaded`` (never
    asked), ``built`` (compiled by this process), ``cached`` (a
    matching artefact was already on disk), ``disabled`` (the
    ``disable_env`` switch), ``build_failed`` or ``load_failed``."""

    def __init__(self, src_rel: str, out_name: str, disable_env: str,
                 configure=None) -> None:
        self.src = os.path.join(REPO, src_rel)
        self.out_name = out_name
        self.disable_env = disable_env
        self._configure = configure  # one-time ctypes signature setup
        self._lock = threading.Lock()
        self._lib: ctypes.CDLL | None = None
        self._tried = False
        self.status = "unloaded"
        self.out: str | None = None  # resolved at load (reads the source)

    def _artefact_path(self) -> str:
        h = hashlib.sha256()
        with open(self.src, "rb") as f:
            h.update(f.read())
        h.update("\0".join(_CXXFLAGS).encode())
        h.update(_cpu_identity())
        stem, ext = os.path.splitext(self.out_name)
        return os.path.join(
            REPO, "native", "build", f"{stem}-{h.hexdigest()[:16]}{ext}"
        )

    def _build(self, out: str) -> bool:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        tmp = f"{out}.tmp.{os.getpid()}"
        try:
            proc = subprocess.run(
                ["g++", *_CXXFLAGS, self.src, "-o", tmp],
                capture_output=True,
                timeout=300,
            )
        except (OSError, subprocess.TimeoutExpired):
            return False
        if proc.returncode != 0:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        os.replace(tmp, out)
        return True

    def load(self) -> ctypes.CDLL | None:
        """The ctypes library, or None when unavailable (``status``
        says why)."""
        if self._lib is not None or self._tried:
            return self._lib
        with self._lock:
            if self._lib is not None or self._tried:
                return self._lib
            self._tried = True
            if os.environ.get(self.disable_env):
                self.status = "disabled"
                return None
            try:
                self.out = self._artefact_path()
            except OSError:
                self.status = "build_failed"  # no source to build from
                return None
            status = "cached"
            if not os.path.exists(self.out):
                if not self._build(self.out):
                    self.status = "build_failed"
                    return None
                status = "built"
            try:
                lib = ctypes.CDLL(self.out)
            except OSError:
                self.status = "load_failed"
                return None
            if self._configure is not None:
                self._configure(lib)
            self._lib = lib
            self.status = status
            return self._lib
