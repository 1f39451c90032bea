"""The one place that builds and loads the native libraries.

``native/<name>.cpp`` builds to ``native/build/lib<name>.so``
(``native/build/`` is not committed) on the first `load` in a fresh
checkout and again when the source is newer than the binary.  The
bindings (``ops/*_native.py``, ``ops/dispatchasm.py``,
``ops/sockwriter.py``, ``ops/sockreader.py``, ``ds/native.py``) hand
`load` their short name and a function that sets ``restype`` /
``argtypes``; a library that
does not build or load is logged once and `load` returns None for the
life of the process: the caller's Python twin serves.

``python -m emqx_tpu.ops.nativelib --rebuild`` is the clean rebuild of
all of them (a toolchain bump, or a copied tree whose mtimes prove
nothing: chip_smoke.py calls `rebuild` for that reason); it imports no
JAX."""

from __future__ import annotations

import ctypes
import fcntl
import glob
import logging
import os
import subprocess
import sys
import threading
from typing import Callable, Dict, Optional

NATIVE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    "native",
)
FLAGS = ("-O3", "-fPIC", "-shared", "-std=c++17", "-Wall", "-pthread")

log = logging.getLogger("emqx_tpu.ops")

_lock = threading.Lock()
# short name -> the bound library, or None once its build or load failed
_libs: Dict[str, Optional[ctypes.CDLL]] = {}


def build(name: str, force: bool = False) -> str:
    """Bring ``lib<name>.so`` up to date and return its path.

    Processes of one checkout (test workers, broker workers) build a
    library once: the compile runs under an exclusive lock on a file
    beside the output and the staleness check is repeated inside it.
    The link goes to a private name and is renamed into place, so a
    process that loads meanwhile sees the old file or the new one,
    never half of one."""
    src = os.path.join(NATIVE, name + ".cpp")
    so = os.path.join(NATIVE, "build", f"lib{name}.so")

    def stale() -> bool:
        return force or not os.path.exists(so) or os.path.getmtime(
            so
        ) < os.path.getmtime(src)

    if stale():
        os.makedirs(os.path.dirname(so), exist_ok=True)
        with open(so + ".lock", "w") as lk:
            fcntl.flock(lk, fcntl.LOCK_EX)
            if stale():
                tmp = f"{so}.{os.getpid()}"
                try:
                    # never on the steady-state path (first load and
                    # source edits only), so the loop stall is accepted
                    # brokerlint: ignore[ASYNC101]
                    subprocess.run(
                        ["g++", *FLAGS, "-o", tmp, src],
                        check=True,
                        capture_output=True,
                    )
                    os.replace(tmp, so)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
    return so


def _why(exc: Exception) -> str:
    err = getattr(exc, "stderr", None)
    return err.decode(errors="replace")[-2000:] if err else repr(exc)


def load(name: str, bind: Callable[[ctypes.CDLL], None]):
    """The bound library, or None when it cannot be built or loaded
    (sticky either way: one dictionary read after the first call)."""
    try:
        return _libs[name]
    except KeyError:
        pass
    with _lock:
        if name not in _libs:
            try:
                lib = ctypes.CDLL(build(name))
                bind(lib)
            except Exception as exc:
                lib = None
                log.error(
                    "native %s did not build or load; "
                    "the Python twin serves: %s", name, _why(exc),
                )
            _libs[name] = lib
        return _libs[name]


def rebuild() -> Dict[str, Optional[str]]:
    """Build every ``native/*.cpp`` anew: its name -> None, or why the
    build failed."""
    why: Dict[str, Optional[str]] = {}
    for src in sorted(glob.glob(os.path.join(NATIVE, "*.cpp"))):
        name = os.path.basename(src)[: -len(".cpp")]
        try:
            build(name, force=True)
            why[name] = None
        except (OSError, subprocess.CalledProcessError) as exc:
            why[name] = _why(exc)
    return why


if __name__ == "__main__":
    if sys.argv[1:] != ["--rebuild"]:
        sys.exit("usage: python -m emqx_tpu.ops.nativelib --rebuild")
    results = rebuild()
    for name, err in results.items():
        if err is None:
            print(f"built build/lib{name}.so")
        else:
            print(f"SKIPPED build/lib{name}.so (build failed; the "
                  f"Python twin will serve)\n{err}", file=sys.stderr)
    sys.exit(1 if any(results.values()) else 0)
