"""Build-and-load for the native (C++) libraries, keyed on content.

A library is built from its committed ``.cpp`` into the source's own
directory under a name that carries a key of everything the binary depends
on: the source bytes, the compiler flags and — because the flags include
``-march=native`` — the host CPU's feature list. Only a file whose name
matches that key is ever loaded, so a binary copied in from another machine
(the tree is copied whole to the chip host), built from an older source, or
left over under the old fixed name is simply not looked at; ``*.so`` is
git-ignored, so a fresh checkout builds from source on first use.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess

CXX = "g++"
CXXFLAGS = ("-O3", "-march=native", "-shared", "-fPIC")
BUILD_TIMEOUT_S = 300


class NativeBuildError(RuntimeError):
    """The toolchain is missing or the build/load failed."""


def _host_id() -> str:
    """What ``-march=native`` binds a binary to: the CPU's feature list."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + " " + line.strip()
    except OSError:
        pass
    return platform.machine() + " " + platform.processor()


def lib_path(src: str, name: str) -> str:
    """Path of the library built from ``src`` with CXXFLAGS on this host."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join((CXX,) + CXXFLAGS).encode())
    h.update(_host_id().encode())
    return os.path.join(os.path.dirname(src),
                        f"lib{name}-{h.hexdigest()[:16]}.so")


def load(src: str, name: str) -> ctypes.CDLL:
    """The library for ``src``, built first unless the keyed file exists."""
    path = lib_path(src, name)
    if not os.path.exists(path):
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            subprocess.run([CXX, *CXXFLAGS, "-o", tmp, src], check=True,
                           capture_output=True, timeout=BUILD_TIMEOUT_S)
            os.replace(tmp, path)       # atomic: racing builders both win
        except (OSError, subprocess.SubprocessError) as e:
            err = getattr(e, "stderr", b"") or b""
            raise NativeBuildError(
                f"building {name} from {src} failed: {e} "
                f"{err.decode(errors='replace')[-400:]}") from e
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        for old in glob.glob(os.path.join(os.path.dirname(src),
                                          f"lib{name}*.so")):
            if old != path:             # superseded keys, the old fixed name
                try:
                    os.unlink(old)
                except OSError:
                    pass
    try:
        return ctypes.CDLL(path)
    except OSError as e:
        raise NativeBuildError(f"loading {path} failed: {e}") from e
