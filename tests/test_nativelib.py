"""The one loader of the native libraries (emqx_tpu/ops/nativelib.py):
built once per checkout however many processes ask, rebuilt when the
source is newer, renamed into place, and a failure is sticky, logged
once and leaves nothing behind."""

import _ctypes
import ctypes
import logging
import multiprocessing
import os
import shutil
import stat
import time

import pytest

from emqx_tpu.ops import nativelib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOURCE = 'extern "C" int answer() { return 42; }\n'

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++ on this machine"
)


def _bind(lib) -> None:
    lib.answer.restype = ctypes.c_int


def _checkout(tmp_path, compiler_body):
    """A ``native/`` holding one small source and no ``build/``, and a
    ``g++`` first on PATH that counts its runs."""
    native = tmp_path / "native"
    native.mkdir()
    (native / "tiny.cpp").write_text(SOURCE)
    bindir = tmp_path / "bin"
    bindir.mkdir()
    counter = tmp_path / "compiler_runs"
    counter.write_text("")
    gxx = bindir / "g++"
    gxx.write_text(f'#!/bin/sh\necho run >> "{counter}"\n{compiler_body}\n')
    gxx.chmod(gxx.stat().st_mode | stat.S_IXUSR)
    path = f"{bindir}{os.pathsep}{os.environ['PATH']}"
    return str(native), path, counter


def _real_compiler() -> str:
    return f'exec "{shutil.which("g++")}" "$@"'


def _runs(counter) -> int:
    return len(counter.read_text().splitlines())


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    native, path, counter = _checkout(tmp_path, _real_compiler())
    monkeypatch.setattr(nativelib, "NATIVE", native)
    monkeypatch.setattr(nativelib, "_libs", {})
    monkeypatch.setenv("PATH", path)
    return native, counter


def _so(native) -> str:
    return os.path.join(native, "build", "libtiny.so")


# ------------------------------------------ (a) six loaders, one build


def _load_in_child(native, path, barrier, results):
    os.environ["PATH"] = path
    nativelib.NATIVE = native
    barrier.wait()
    lib = nativelib.load("tiny", _bind)
    results.put(None if lib is None else lib.answer())


def test_six_processes_build_once_and_all_load(tmp_path):
    native, path, counter = _checkout(tmp_path, _real_compiler())
    ctx = multiprocessing.get_context("spawn")
    barrier, results = ctx.Barrier(6), ctx.Queue()
    children = [
        ctx.Process(
            target=_load_in_child, args=(native, path, barrier, results)
        )
        for _ in range(6)
    ]
    for c in children:
        c.start()
    answers = [results.get(timeout=120) for _ in children]
    for c in children:
        c.join(30)
        assert c.exitcode == 0
    assert answers == [42] * 6
    assert _runs(counter) == 1
    assert sorted(os.listdir(os.path.join(native, "build"))) == [
        "libtiny.so", "libtiny.so.lock",
    ]


# ------------------------------------------------- (b) the staleness rule


def test_rebuilt_when_the_source_is_newer_and_only_then(checkout):
    native, counter = checkout
    so = nativelib.build("tiny")
    assert so == _so(native) and _runs(counter) == 1
    os.utime(
        os.path.join(native, "tiny.cpp"),
        (time.time() - 3600, time.time() - 3600),
    )
    assert nativelib.build("tiny") == so and _runs(counter) == 1
    os.utime(
        os.path.join(native, "tiny.cpp"),
        (time.time() + 3600, time.time() + 3600),
    )
    nativelib.build("tiny")
    assert _runs(counter) == 2
    nativelib.build("tiny", force=True)
    assert _runs(counter) == 3


# --------------------------------------------- (c) a compiler that fails


def test_failed_build_is_sticky_logged_once_and_leaves_no_file(
    tmp_path, monkeypatch, caplog
):
    native, path, counter = _checkout(
        tmp_path, 'echo "tiny.cpp:1: error: injected" >&2\n'
                  'for a; do case "$prev" in -o) echo half > "$a";; esac; '
                  'prev="$a"; done\nexit 1',
    )
    monkeypatch.setattr(nativelib, "NATIVE", native)
    monkeypatch.setattr(nativelib, "_libs", {})
    monkeypatch.setenv("PATH", path)
    with caplog.at_level(logging.ERROR, logger="emqx_tpu.ops"):
        assert nativelib.load("tiny", _bind) is None
        assert nativelib.load("tiny", _bind) is None
    assert _runs(counter) == 1
    records = [r for r in caplog.records if "tiny" in r.getMessage()]
    assert len(records) == 1
    assert "error: injected" in records[0].getMessage()
    # the half-written output of the failed compiler is gone too
    assert os.listdir(os.path.join(native, "build")) == ["libtiny.so.lock"]
    assert nativelib.rebuild() == {
        "tiny": 'tiny.cpp:1: error: injected\n'
    }


def test_a_library_that_does_not_bind_is_a_failed_load(checkout, caplog):
    def bind(lib):
        lib.no_such_symbol.restype = ctypes.c_int

    with caplog.at_level(logging.ERROR, logger="emqx_tpu.ops"):
        assert nativelib.load("tiny", bind) is None
    assert nativelib.load("tiny", _bind) is None  # sticky
    assert len(caplog.records) == 1


# ------------------------------- (d) a reader beside a rebuilding writer


def _rebuild_in_child(native, path, rounds, started):
    os.environ["PATH"] = path
    nativelib.NATIVE = native
    started.set()
    for _ in range(rounds):
        nativelib.build("tiny", force=True)


def test_reader_sees_a_whole_library_while_another_process_rebuilds(
    checkout,
):
    native, counter = checkout
    so = nativelib.build("tiny")
    ctx = multiprocessing.get_context("spawn")
    started = ctx.Event()
    writer = ctx.Process(
        target=_rebuild_in_child,
        args=(native, os.environ["PATH"], 8, started),
    )
    writer.start()
    assert started.wait(60)
    loads, inodes = 0, set()
    while writer.is_alive():
        inodes.add(os.stat(so).st_ino)
        lib = ctypes.CDLL(so)  # raises on a truncated or absent file
        _bind(lib)
        assert lib.answer() == 42
        _ctypes.dlclose(lib._handle)  # so that the next CDLL opens anew
        loads += 1
    writer.join()
    assert writer.exitcode == 0
    assert _runs(counter) == 9
    assert loads > 8 and len(inodes) > 1  # it did read across renames


# ------------------------------------- (e) one statement of the decision


def test_the_compiler_and_its_flags_are_named_once():
    hits = {'"g++"': [], '"-fPIC"': []}
    for top in ("emqx_tpu", "native"):
        for dirpath, dirnames, files in os.walk(os.path.join(REPO, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                if not f.endswith((".py", ".sh")):
                    continue
                path = os.path.join(dirpath, f)
                with open(path, encoding="utf-8") as fh:
                    text = fh.read()
                for needle in hits:
                    hits[needle] += [path] * text.count(needle)
    loader = os.path.join(REPO, "emqx_tpu", "ops", "nativelib.py")
    assert hits == {'"g++"': [loader], '"-fPIC"': [loader]}


def test_every_binding_loads_through_the_loader():
    """The seven bindings keep their module-level ``load()`` (the
    benchmark and chip_smoke.py call them by name) and hand back the
    loader's own object."""
    from emqx_tpu.ds import native as dslog
    from emqx_tpu.ops import dispatchasm, sockreader, sockwriter
    from emqx_tpu.ops import sortutil_native
    from emqx_tpu.ops import tokdict_native, trie_native

    for name, mod in (
        ("hosttrie", trie_native), ("sortutil", sortutil_native),
        ("tokdict", tokdict_native), ("dispatchasm", dispatchasm),
        ("dslog", dslog), ("sockwriter", sockwriter),
        ("sockreader", sockreader),
    ):
        lib = mod.load()
        assert lib is not None, name
        assert lib is nativelib._libs[name] is mod.load()
        assert os.path.samefile(
            lib._name,
            os.path.join(REPO, "native", "build", f"lib{name}.so"),
        )
