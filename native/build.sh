#!/usr/bin/env bash
# Build every native helper .so from the committed sources with the
# flags the on-demand builders in emqx_tpu/ops/*_native.py,
# ops/dispatchasm.py, ops/sockwriter.py and ds/native.py use.  native/build/ is not
# committed: each loader builds its own lib on first load and again
# when the source is newer than the binary, so this script is for a
# clean rebuild, a toolchain bump, or a copied tree whose mtimes prove
# nothing (chip_smoke.py runs it for that reason).
#
# A lib that fails to build is reported and SKIPPED: every native lib
# has a pure-Python fallback, and tier-1 skips the native parity tests
# when the lib is absent (mirroring tests/test_tokdict_native.py).
# All sources are C++17-only by design (hosttrie's old heterogeneous
# unordered_map lookup needed GCC >= 11 and was rewritten), so any
# toolchain this repo meets builds every lib.

set -u
cd "$(dirname "$0")"
mkdir -p build

FLAGS="-O3 -fPIC -shared -std=c++17 -Wall -pthread"
status=0

for src in sortutil tokdict dslog hosttrie dispatchasm sockwriter; do
    out="build/lib${src}.so"
    # link under a private name and rename into place: a process that
    # loads the library meanwhile sees the old file or the new one,
    # never half of one
    if g++ $FLAGS -o "$out.$$" "${src}.cpp" && mv -f "$out.$$" "$out"; then
        echo "built $out"
    else
        rm -f "$out.$$"
        echo "SKIPPED $out (build failed; pure-Python fallback will serve)" >&2
        status=1
    fi
done

exit $status
