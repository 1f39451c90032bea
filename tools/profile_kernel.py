"""Kernel profiling harness (dev tool, not part of the framework).

Builds (once, cached to .profile_cache2.npz) the bench.py 10M-sub
automaton + encoded topic streams, then times the production
match_batch on the real device across f_width/m_cap settings.

Timing notes: `block_last` (dispatch all batches, block on the final
output) is the device-compute proxy; `fetch_all` adds one serialized
device->host round-trip per batch and overstates steady-state cost
(production overlaps transfers).

Usage: python tools/profile_kernel.py [f_width ...]
"""

import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from bench import make_filters, make_topics
from emqx_tpu import topic as T
from emqx_tpu.ops.automaton import build_automaton
from emqx_tpu.ops.dictionary import TokenDict, encode_topics
from emqx_tpu.ops.match_kernel import match_batch

CACHE = os.path.join(os.path.dirname(__file__), ".profile_cache2.npz")
N_SUBS = int(os.environ.get("PROF_SUBS", 10_000_000))
BATCH = int(os.environ.get("PROF_BATCH", 32768))
ITERS = int(os.environ.get("PROF_ITERS", 30))
M_CAP = int(os.environ.get("PROF_M", 16))


def log(m):
    print(m, file=sys.stderr, flush=True)


def load_or_build():
    if os.path.exists(CACHE):
        return dict(np.load(CACHE, allow_pickle=False))
    t0 = time.perf_counter()
    filters, pops = make_filters(N_SUBS, 8)
    tdict = TokenDict()
    aut = build_automaton(filters, tdict, max_levels=16)
    log(f"built: nodes={aut.n_nodes} buckets={len(aut.fp_rows)} "
        f"salt={aut.salt} levels={aut.kernel_levels} "
        f"in {time.perf_counter()-t0:.1f}s")
    rng = np.random.default_rng(0)
    toks, lens, dols = [], [], []
    for _ in range(ITERS):
        s = make_topics(rng, BATCH, pops)
        tk, ln, dl = encode_topics(tdict, [T.words(t) for t in s],
                                   aut.kernel_levels)
        toks.append(tk); lens.append(ln); dols.append(dl)
    data = dict(
        fp_rows=aut.fp_rows, node_rows=aut.node_rows,
        salt=np.uint32(aut.salt),
        toks=np.stack(toks), lens=np.stack(lens), dols=np.stack(dols),
    )
    np.savez_compressed(CACHE, **data)
    return data


def main():
    d = load_or_build()
    log(f"buckets={len(d['fp_rows'])} nodes={len(d['node_rows'])} "
        f"salt={int(d['salt'])} platform={jax.devices()[0].platform}")
    dev = (jax.device_put(d["fp_rows"]), jax.device_put(d["node_rows"]),
           jax.device_put(d["salt"].reshape(())))
    streams = [(d["toks"][i], d["lens"][i], d["dols"][i])
               for i in range(len(d["toks"]))]

    widths = [int(w) for w in (sys.argv[1:] or ["4", "8"])]
    for fw in widths:
        fn = partial(match_batch, f_width=fw, m_cap=M_CAP)
        o = fn(*dev, *streams[0])
        np.asarray(o[1])  # compile + settle queue
        for _rep in range(2):  # second rep = steady state
            t0 = time.perf_counter()
            outs = [fn(*dev, tk, ln, dl) for tk, ln, dl in streams]
            jax.block_until_ready(outs[-1])
            t_blocklast = time.perf_counter() - t0
            total = sum(int(np.asarray(x[1]).sum()) for x in outs)
            dt = time.perf_counter() - t0
        ovf = sum(int(np.asarray(o[2]).sum()) for o in outs)
        n = BATCH * len(streams)
        log(f"f_width={fw:2d}  block_last {t_blocklast:.3f}s "
            f"({n / t_blocklast:12,.0f} topics/s)  fetch_all {dt:.3f}s  "
            f"matches={total} ovf={ovf}")


if __name__ == "__main__":
    main()
