"""Stage-level profile of the bench full path on the real chip:
where does the non-device cost of a batch go?  Candidates: Python
tokenize loop, np.unique, device dispatch, device->host code transfer
(link bandwidth), CSR expand, fid gather."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax

from bench import make_filters, make_topics
from emqx_tpu import topic as T
from emqx_tpu.ops.automaton import build_automaton, expand_codes_flat
from emqx_tpu.engine import _pad_batch
from emqx_tpu.ops.dictionary import PAD_TOK, TokenDict
from emqx_tpu.ops.match_kernel import match_batch, match_batch_compact

n_subs = int(os.environ.get("P_SUBS", 1_000_000))
batch = int(os.environ.get("P_BATCH", 32768))
iters = int(os.environ.get("P_ITERS", 12))
f_width, m_cap = 4, 16

print(f"platform={jax.devices()[0].platform}", flush=True)

# link bandwidth probe: time device->host of known sizes
x = jax.device_put(np.zeros((1 << 20,), np.int32))  # 4 MB
np.asarray(x)
t0 = time.perf_counter(); np.asarray(x); bw4 = 4 / (time.perf_counter() - t0)
y = jax.device_put(np.zeros((1 << 18,), np.int32))  # 1 MB
np.asarray(y)
t0 = time.perf_counter(); np.asarray(y); bw1 = 1 / (time.perf_counter() - t0)
tiny = jax.jit(lambda a: a + 1); ta = jax.device_put(np.zeros(8, np.int32))
np.asarray(tiny(ta))
t0 = time.perf_counter()
for _ in range(5): np.asarray(tiny(ta))
rtt = (time.perf_counter() - t0) / 5 * 1e3
print(f"d2h bandwidth: 4MB={bw4:.1f} MB/s 1MB={bw1:.1f} MB/s rtt={rtt:.0f} ms", flush=True)

rng = np.random.default_rng(0)
filters, pops = make_filters(n_subs, 8)
tdict = TokenDict()
t0 = time.perf_counter()
aut = build_automaton(filters, tdict, max_levels=16)
print(f"build {time.perf_counter()-t0:.1f}s nodes={aut.n_nodes}", flush=True)
dev = tuple(jax.device_put(a) for a in aut.device_arrays())
fid_arr = np.arange(n_subs, dtype=np.int64)
streams = [make_topics(rng, batch, pops) for _ in range(iters)]
levels = aut.kernel_levels

enc_index = {}; enc_mat = np.full((65536, levels), PAD_TOK, np.int32)
enc_len = np.zeros(65536, np.int32); enc_dol = np.zeros(65536, bool)
used = 0
S = dict(tok=0.0, uniq=0.0, dispatch=0.0, xfer=0.0, expand=0.0, gather=0.0)

def submit(ts):
    global used, enc_mat, enc_len, enc_dol
    t0 = time.perf_counter()
    idx = np.empty(len(ts), np.int64)
    get = tdict.get
    for i, t in enumerate(ts):
        j = enc_index.get(t)
        if j is None:
            ws = T.words(t)
            n = min(len(ws), levels)
            row = enc_mat[used]; row[:] = PAD_TOK
            for k in range(n): row[k] = get(ws[k])
            enc_len[used] = n; enc_dol[used] = ws[0].startswith("$")
            j = enc_index[t] = used; used += 1
        idx[i] = j
    S["tok"] += time.perf_counter() - t0
    t0 = time.perf_counter()
    uniq, inv = np.unique(idx, return_inverse=True)
    tokens, lengths, dollar = _pad_batch(enc_mat[uniq], enc_len[uniq], enc_dol[uniq])
    S["uniq"] += time.perf_counter() - t0
    t0 = time.perf_counter()
    out = match_batch_compact(*dev, tokens, lengths, dollar, f_width=f_width, m_cap=m_cap, c_cap=tokens.shape[0])
    out[0].copy_to_host_async(); out[1].copy_to_host_async(); out[2].copy_to_host_async()
    S["dispatch"] += time.perf_counter() - t0
    return out, len(uniq), inv, tokens.shape

def drain(p):
    out, n_uniq, inv, shp = p
    t0 = time.perf_counter()
    flat = np.asarray(out[0]); counts = np.asarray(out[1]).astype(np.int64)
    assert int(np.asarray(out[2])[0]) <= len(flat), "compact clip"
    S["xfer"] += time.perf_counter() - t0
    t0 = time.perf_counter()
    ovf_u = counts < 0
    rows, pos = expand_codes_flat(aut.code_off, aut.code_idx, flat,
                                  np.where(ovf_u, -counts-1, counts), inv)
    codes = flat
    S["expand"] += time.perf_counter() - t0
    t0 = time.perf_counter()
    fids = fid_arr[pos]
    S["gather"] += time.perf_counter() - t0
    return rows, fids, codes.shape, int((codes >= 0).sum())

# warm
drain(submit(streams[0]))
for k in S: S[k] = 0.0

from collections import deque
depth = 8
inflight = deque(); t_start = time.perf_counter(); nvalid = 0; shp = None
for s in streams:
    inflight.append(submit(s))
    if len(inflight) >= depth:
        _, _, shp, nv = drain(inflight.popleft()); nvalid += nv
while inflight:
    _, _, shp, nv = drain(inflight.popleft()); nvalid += nv
el = time.perf_counter() - t_start
print(f"full path: {batch*iters/el:,.0f} topics/s ({el/iters*1e3:.1f} ms/batch)", flush=True)
print(f"codes shape/batch={shp} valid codes/batch={nvalid/iters:,.0f}", flush=True)
for k, v in S.items():
    print(f"  {k:9s} {v/iters*1e3:7.2f} ms/batch", flush=True)

# device-only for comparison
enc = []
for s in streams:
    idx = np.array([enc_index[t] for t in s]); u, _ = np.unique(idx, return_inverse=True)
    enc.append(_pad_batch(enc_mat[u], enc_len[u], enc_dol[u]))
match_batch(*dev, *enc[0], f_width=f_width, m_cap=m_cap)[1].block_until_ready()
t0 = time.perf_counter()
outs = [match_batch(*dev, *e, f_width=f_width, m_cap=m_cap) for e in enc]
outs[-1][1].block_until_ready()
el = time.perf_counter() - t0
print(f"device-only(dedup): {batch*iters/el:,.0f} topics/s ({el/iters*1e3:.1f} ms/batch)", flush=True)
