"""Build step for the package's native helpers (search/csrc/sim.cc,
runtime/csrc/dataloader.cc): plain C ABI shared objects compiled with g++
on first use and loaded through ctypes.

The binary's name carries the hash of what it was built from, so the
library a process loads always matches the tracked source — a checkout
copied without file times, or one that carries a stale `.so`, rebuilds
instead of loading the wrong code.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
from typing import Sequence

_CXX = ("g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-shared")


def build_native_lib(src: str, stem: str,
                     extra_flags: Sequence[str] = ()) -> str:
    """Compile ``src`` into ``<dir of src>/<stem>.<content hash>.so`` unless
    that exact file already exists, and return its path. Raises
    FileNotFoundError when there is no g++ and CalledProcessError when the
    compile fails. The build goes to a temp file + os.replace so concurrent
    processes sharing the package dir never dlopen a half-written file;
    binaries of other source versions are removed after a build."""
    cmd = [*_CXX, *extra_flags]
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(cmd).encode()).hexdigest()
    out_dir = os.path.dirname(os.path.abspath(src))
    lib = os.path.join(out_dir, f"{stem}.{digest[:16]}.so")
    if os.path.exists(lib):
        return lib
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run([*cmd, "-o", tmp, src], check=True,
                       capture_output=True)
        os.replace(tmp, lib)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for old in glob.glob(os.path.join(out_dir, f"{stem}.*.so")):
        if old != lib:
            try:
                os.remove(old)
            except OSError:
                pass  # another process holds or already removed it
    return lib
