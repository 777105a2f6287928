"""Our own LZ77 codec ("repro-lz") — the dictionary-coding substrate the
paper's Zstd stage is built from (§3.2.2: ``C_zstd = FSE(LZ77(T, W, L))``).

The wire format is LZ4-block-style: greedy hash-table match finding,
min-match 4, 64 KiB window, sequences of

    [token: litlen<<4 | (matchlen-4)] [litlen ext*] [literals]
    [offset u16le] [matchlen ext*]

with a final literals-only sequence.

Two implementations share that wire format:

* the **scalar** path — the original pure-Python greedy loop, kept
  byte-for-byte as the reference oracle and used for small payloads
  (below ``_NP_MIN_COMPRESS``/``_NP_MIN_DECOMPRESS``) where NumPy's
  fixed per-call overhead loses to the tight loop;
* the **vectorized** path — match candidates from a hashed head-table
  filled block-by-block with NumPy scatter/gather (plus short-period
  run detection), match lengths from batched 8-byte-gram XOR rounds,
  greedy selection as a tiny Python jump loop over precomputed arrays,
  and the sequence stream emitted with fused cumsum/scatter passes.
  Output is a valid stream of the same format (round-trip-identical);
  the exact byte stream may differ from the scalar parse because the
  vectorized candidate table sees *every* position while the scalar
  loop seeds sparsely inside matches.

A third, **device** variant reuses the vectorized path's candidate
contract but runs the match-finding stage (gram/hash build, head-table
scatter, batched extension) on the accelerator via
``repro.kernels.lz_match``; greedy selection and sequence emit are the
*same host code* as the vectorized path, so its output is byte-identical
to the vectorized parse.

Every path decodes the others' output — the format carries no
producer mark.  ``REPRO_LZ_MODE=scalar|vector|device|auto`` (env)
forces a path; ``auto`` (default) routes on payload size and a cheap
byte-run probe (run-dominated inputs like zero pages stay scalar, whose
skip-ahead loop beats any per-position vectorization), and takes the
device match finder only when a non-CPU backend is attached and the
payload clears ``REPRO_LZ_DEVICE_MIN`` (see ``repro.core.device``).

Dictionary (prefix) mode: ``lz_compress(data, prefix=d)`` seeds the
match window with ``d`` — matches may reach back into the dictionary,
which is exactly how zstd's trained-dictionary mode recovers
cross-record redundancy for payloads too short to build their own
window.  The output covers only ``data``; ``lz_decompress(comp,
prefix=d)`` must be handed the identical dictionary (the codec layer
threads a fingerprint through frame headers to guarantee that).
"""

from __future__ import annotations

import threading
from array import array

import numpy as np

from repro.core import env

_MIN_MATCH = 4
_WINDOW = 0xFFFF  # 64 KiB - 1, max encodable offset
_HASH_MASK = (1 << 20) - 1

# -- vectorized-path tuning ------------------------------------------------
_NP_MIN_COMPRESS = 2048     # payload bytes below which scalar compress wins
_NP_MIN_DECOMPRESS = 4096   # compressed bytes below which scalar decode wins
_HASH_BITS = 20             # head-table size (2^bits int32 entries)
_HASH_MUL = np.uint32(2654435761)
_SCAN_BLOCK = 1024          # head-table scatter granularity: candidates are
                            # invisible within the same block (run detection
                            # catches the short-period ones); smaller blocks
                            # buy ~1% ratio for measurably slower scans
_EXT_ROUNDS = 3             # eager extension: 8-byte grams, cap 4+8*rounds
_RUN_PROBE = 8192           # bytes sampled by the run-dominance probe
_DEVICE_MIN_COMPRESS = 1 << 20   # auto-mode device crossover (bytes): the
                            # candidate stage must amortize the byte
                            # upload + ok/cand/mlen download; an estimate,
                            # not a chip measurement (REPRO_LZ_DEVICE_MIN)
_DECODE_MAX_ROUNDS = 64     # frontier-batch rounds before python fallback

# Seeded match tables per dictionary (scalar path): a dict-primed compress
# call would otherwise re-hash every prefix position per record — per-record
# O(dict) setup across a whole shard.  Small bounded memo; entries are
# copied per call because compression mutates the table.  The lock matters:
# parallel compactions (per-shard locks allow them) score dict candidates
# concurrently, and unsynchronized eviction could double-pop.
_PREFIX_TABLES: dict = {}
_PREFIX_TABLES_MAX = 8
_PREFIX_TABLES_LOCK = threading.Lock()


def _lz_mode() -> str:
    return env.read("REPRO_LZ_MODE")


def _seeded_table(prefix: bytes) -> dict:
    """Match-table entries fully inside the prefix (data-independent, so
    cacheable); the caller adds the few positions whose keys straddle the
    prefix/payload boundary."""
    with _PREFIX_TABLES_LOCK:
        cached = _PREFIX_TABLES.get(prefix)
        if cached is None:
            cached = {}
            for j in range(0, max(len(prefix) - _MIN_MATCH + 1, 0)):
                cached[prefix[j : j + _MIN_MATCH]] = j
            while len(_PREFIX_TABLES) >= _PREFIX_TABLES_MAX:
                _PREFIX_TABLES.pop(next(iter(_PREFIX_TABLES)))
            _PREFIX_TABLES[prefix] = cached
        return dict(cached)


def _ext_len(value: int) -> bytes:
    """LZ4-style length extension: 255-run + remainder."""
    out = bytearray()
    while value >= 255:
        out.append(255)
        value -= 255
    out.append(value)
    return bytes(out)


def _match_len(data: bytes, a: int, b: int, n: int) -> int:
    """Length of the common run data[a:] == data[b:] (a < b), capped at n-b."""
    l = 0
    step = 64
    while b + l + step <= n and data[a + l : a + l + step] == data[b + l : b + l + step]:
        l += step
    while b + l < n and data[a + l] == data[b + l]:
        l += 1
    return l


def _match_len_fast(buf: bytes, a: int, b: int, n: int) -> int:
    """`_match_len` via doubling + bisection on C-level slice compares —
    used by the vectorized path's lazy tail extension, where matches are
    long and the per-byte loop would dominate."""
    cap = n - b
    lo, step = 0, 64
    while lo + step <= cap and buf[a + lo : a + lo + step] == buf[b + lo : b + lo + step]:
        lo += step
        step <<= 1
    hi = min(lo + step, cap)
    while lo < hi:
        mid = (lo + hi + 1) >> 1
        if buf[a + lo : a + mid] == buf[b + lo : b + mid]:
            lo = mid
        else:
            hi = mid - 1
    return lo


def _only_literals(buf: bytes, plen: int, n: int) -> bytes:
    out = bytearray()
    lit_len = n - plen
    tok_lit = min(lit_len, 15)
    out.append(tok_lit << 4)
    if tok_lit == 15:
        out += _ext_len(lit_len - 15)
    out += buf[plen:n]
    return bytes(out)


# ---------------------------------------------------------------------------
# Scalar path (reference oracle)
# ---------------------------------------------------------------------------


def _lz_compress_scalar(data: bytes, prefix: bytes = b"") -> bytes:
    """Greedy single-pass LZ77; returns self-contained block.

    ``prefix`` seeds the window without being emitted: matches may start
    inside it (offsets reach at most ``_WINDOW`` back), so short payloads
    that share structure with the dictionary compress to a few
    dict-offset matches.  ``prefix=b""`` is byte-identical to the
    historical no-dictionary behavior.
    """
    plen = len(prefix)
    buf = prefix + data if plen else data
    n = len(buf)
    out = bytearray()
    if n == plen:
        return bytes(out)
    limit = n - _MIN_MATCH
    # seed the table with every dictionary position (last occurrence wins:
    # closest candidate, shortest offsets); the fully-in-prefix entries
    # come from a per-dictionary memo, only the boundary-straddling keys
    # depend on the payload
    if plen:
        table = _seeded_table(prefix)
        for j in range(max(plen - _MIN_MATCH + 1, 0), min(plen, limit + 1)):
            table[buf[j : j + _MIN_MATCH]] = j
    else:
        table = {}
    i = plen
    lit_start = plen
    # leave the last MIN_MATCH bytes as literals (simplifies the tail)
    while i <= limit:
        key = buf[i : i + _MIN_MATCH]
        cand = table.get(key)
        table[key] = i
        if cand is not None and i - cand <= _WINDOW:
            mlen = _match_len(buf, cand, i, n)
            if mlen >= _MIN_MATCH:
                lit_len = i - lit_start
                offset = i - cand
                tok_lit = min(lit_len, 15)
                tok_match = min(mlen - _MIN_MATCH, 15)
                out.append((tok_lit << 4) | tok_match)
                if tok_lit == 15:
                    out += _ext_len(lit_len - 15)
                out += buf[lit_start:i]
                out.append(offset & 0xFF)
                out.append(offset >> 8)
                if tok_match == 15:
                    out += _ext_len(mlen - _MIN_MATCH - 15)
                # seed the table sparsely inside the match (speed/ratio balance)
                end = i + mlen
                for j in range(i + 1, min(end, limit), 7):
                    table[buf[j : j + _MIN_MATCH]] = j
                i = end
                lit_start = i
                continue
        i += 1
    # final literals-only sequence
    lit_len = n - lit_start
    tok_lit = min(lit_len, 15)
    out.append(tok_lit << 4)
    if tok_lit == 15:
        out += _ext_len(lit_len - 15)
    out += buf[lit_start:n]
    return bytes(out)


def _lz_decompress_scalar(comp: bytes, prefix: bytes = b"") -> bytes:
    out = bytearray(prefix)
    plen = len(prefix)
    i, n = 0, len(comp)
    if n == 0:
        return b""
    ended = False
    while i < n:
        token = comp[i]
        i += 1
        lit_len = token >> 4
        if lit_len == 15:
            while True:
                if i >= n:
                    raise ValueError("corrupt LZ stream: truncated")
                b = comp[i]
                i += 1
                lit_len += b
                if b != 255:
                    break
        if i + lit_len > n:
            raise ValueError("corrupt LZ stream: truncated")
        if lit_len:
            out += comp[i : i + lit_len]
            i += lit_len
        if i >= n:  # final sequence: literals only
            ended = True
            break
        if i + 2 > n:
            raise ValueError("corrupt LZ stream: truncated")
        offset = comp[i] | (comp[i + 1] << 8)
        i += 2
        mlen = (token & 0xF) + _MIN_MATCH
        if (token & 0xF) == 15:
            while True:
                if i >= n:
                    raise ValueError("corrupt LZ stream: truncated")
                b = comp[i]
                i += 1
                mlen += b
                if b != 255:
                    break
        if offset == 0:
            raise ValueError("corrupt LZ stream: zero offset")
        start = len(out) - offset
        if start < 0:
            raise ValueError("corrupt LZ stream: offset before start")
        if offset >= mlen:
            out += out[start : start + mlen]
        else:
            # overlapping copy: the pattern repeats with period `offset`
            seg = bytes(out[start:])
            reps = mlen // offset + 1
            out += (seg * reps)[:mlen]
    if not ended:
        # a valid block always ends with a literals-only sequence (the
        # encoder emits one even when empty); stopping right after a match
        # means the tail was cut off
        raise ValueError("corrupt LZ stream: truncated")
    return bytes(out[plen:])


# ---------------------------------------------------------------------------
# Vectorized path
# ---------------------------------------------------------------------------


def _candidates_np(buf: bytes, plen: int, n: int):
    """Candidate stage of the vectorized parse: hashed head-table
    candidates + batched 8-byte-gram extension.

    Returns ``(ok, cand, mlen)`` over the ``n - 3`` positions holding a
    full 4-gram: ``ok`` marks positions with a verified in-window
    candidate, ``cand`` its source position, ``mlen`` the match length —
    exact when positive, a *lazy* marker when negative (cap survivors and
    out-of-room tails; ``_select_emit`` resolves those by memcmp).  The
    device match finder (``repro.kernels.lz_match``) produces the same
    contract, so both feed one shared selection/emit."""
    nv = n - 3   # positions holding a full 4-gram (valid match starts)
    n8 = n - 7   # positions holding a full 8-gram (extension bound)
    # every 4-gram as a little-endian uint32, via a 1-byte-strided view
    # (x86/ARM handle the unaligned loads; the copy aligns for gathers)
    v = np.ascontiguousarray(
        np.ndarray(shape=(nv,), dtype="<u4", buffer=buf, strides=(1,)))
    h = ((v * _HASH_MUL) >> np.uint32(32 - _HASH_BITS)).astype(np.intp)

    # head-table scatter, one block at a time: candidates always come from
    # an earlier block (`cand` read before `head` update), so a position
    # never proposes itself; duplicate hashes within a block resolve
    # last-wins, matching the "closest candidate" policy
    cand = np.empty(nv, np.intp)
    head = np.full(1 << _HASH_BITS, -1, np.intp)
    idx = np.arange(nv, dtype=np.intp)
    for a in range(0, nv, _SCAN_BLOCK):
        b = a + _SCAN_BLOCK
        hb = h[a:b]
        cand[a:b] = head[hb]
        head[hb] = idx[a:b]

    # short-period runs are invisible to the block scatter (same block) —
    # catch them directly: d=4 covers periods 1/2/4, d=3 period 3; nearer
    # candidates overwrite the cross-block ones (shorter offsets)
    eq = v[4:] == v[:-4]
    cand[4:][eq] = idx[:-4][eq]
    eq = v[3:] == v[:-3]
    cand[3:][eq] = idx[:-3][eq]

    # verify: exact 4-gram equality kills hash collisions; window-check
    ok = (cand >= 0) & (idx - cand <= _WINDOW) & (v[np.maximum(cand, 0)] == v)
    if plen:
        ok[:plen] = False  # matches may start only in the payload

    # eager extension: compare 8-byte grams at l, l+8, ...; a mismatching
    # gram contributes its common low-end bytes exactly (XOR trailing
    # zero-byte count), so mlen below the cap is exact.  Positions that hit
    # the cap, ran past the gram bound, or belong to a run-dominated input
    # (survivor set not shrinking) fall back to lazy memcmp extension at
    # selection time — long matches amortize it.
    v8 = np.ascontiguousarray(
        np.ndarray(shape=(n8,), dtype="<u8", buffer=buf, strides=(1,))) \
        if n8 > 0 else np.zeros(0, np.uint64)
    i_act = np.flatnonzero(ok)
    mlen = np.zeros(nv, np.int64)
    mlen[i_act] = _MIN_MATCH
    c_act = cand[i_act]
    l = _MIN_MATCH
    lazy_tails = []
    for _ in range(_EXT_ROUNDS):
        if not i_act.size or n8 <= 0:
            break
        # i_act is ascending, so positions whose next gram would run off
        # the buffer form a suffix — they go straight to the lazy path
        k = int(np.searchsorted(i_act, n8 - l))
        if k < i_act.size:
            lazy_tails.append(i_act[k:])
            i_act = i_act[:k]
            c_act = c_act[:k]
            if not i_act.size:
                break
        d8 = v8[i_act + l] ^ v8[c_act + l]
        full = d8 == 0
        part = ~full
        dp = d8[part]
        # exact extra bytes from the mismatching gram: exponent of its
        # lowest set bit in bytes (float64-mantissa trick, branch-free)
        lsb = (dp & (np.uint64(0) - dp)).astype(np.float64)
        mlen[i_act[part]] += ((lsb.view(np.int64) >> 52) - 1023) >> 3
        i_act = i_act[full]
        c_act = c_act[full]
        mlen[i_act] += 8
        l += 8
        if i_act.size * 2 > ok.size:  # run-dominated: stop burning rounds
            break
    # lazy marker (negative mlen): cap survivors + extensions that ran out
    # of gram room before finding a mismatch
    if i_act.size:
        mlen[i_act] *= -1
    for lt in lazy_tails:
        mlen[lt] *= -1
    return ok, cand, mlen


def _select_emit(buf: bytes, plen: int, n: int, ok: np.ndarray,
                 cand: np.ndarray, mlen: np.ndarray) -> bytes:
    """Greedy selection + fused sequence emit over a candidate triple
    (shared by the NumPy and device match finders — this is the half that
    freezes the wire format)."""
    arr = np.frombuffer(buf, np.uint8)
    nv = n - 3
    # greedy selection: ok-byte probe + match-length jumps.  178K-sequence
    # streams spend ~60ms here; everything the loop touches is O(1) —
    # bytes for the candidate test, a C array for lengths.
    ok_b = ok.tobytes()  # bool -> \x00/\x01 bytes
    ml_a = array("q")
    ml_a.frombytes(mlen.tobytes())
    seq_pos: list = []
    seq_ml: list = []
    ap = seq_pos.append
    am = seq_ml.append
    i = plen
    while i < nv:
        if not ok_b[i]:
            i += 1
            continue
        m = ml_a[i]
        if m <= 0:
            m = _MIN_MATCH + _match_len_fast(
                buf, int(cand[i]) + _MIN_MATCH, i + _MIN_MATCH, n)
        ap(i)
        am(m)
        i += m
    S = len(seq_pos)
    if S == 0:
        return _only_literals(buf, plen, n)

    # fused emit: all sequence fields as arrays, one cumsum for the layout,
    # span-fills for ext runs, one gather/scatter for the literals
    mp = np.array(seq_pos, dtype=np.int64)
    ml = np.array(seq_ml, dtype=np.int64)
    ls = np.empty(S, np.int64)
    ls[0] = plen
    ls[1:] = mp[:-1] + ml[:-1]
    ll = mp - ls
    off = (mp - cand[mp]).astype(np.int64)
    tok_lit = np.minimum(ll, 15)
    tok_match = np.minimum(ml - _MIN_MATCH, 15)
    token = (tok_lit << 4) | tok_match
    vl = ll - 15
    el = np.where(ll >= 15, vl // 255 + 1, 0)          # lit ext byte counts
    vm = ml - _MIN_MATCH - 15
    em = np.where(ml - _MIN_MATCH >= 15, vm // 255 + 1, 0)
    starts = np.zeros(S + 1, np.int64)
    np.cumsum(1 + el + ll + 2 + em, out=starts[1:])
    out = np.zeros(int(starts[-1]), np.uint8)
    st = starts[:-1]
    out[st] = token
    he = np.flatnonzero(el)
    if he.size:
        e_st = st[he] + 1
        e_len = el[he]
        fill = (np.repeat(e_st - np.cumsum(e_len) + e_len, e_len)
                + np.arange(int(e_len.sum())))
        out[fill] = 255
        out[e_st + e_len - 1] = (vl[he] % 255).astype(np.uint8)
    lit_dst = st + 1 + el
    if int(ll.sum()):
        nz = np.flatnonzero(ll)
        lln = ll[nz]
        csum = np.cumsum(lln)
        ar = np.arange(int(csum[-1]))
        out[np.repeat(lit_dst[nz] - csum + lln, lln) + ar] = \
            arr[np.repeat(ls[nz] - csum + lln, lln) + ar]
    op = lit_dst + ll
    out[op] = off & 0xFF
    out[op + 1] = off >> 8
    hm = np.flatnonzero(em)
    if hm.size:
        e_st = op[hm] + 2
        e_len = em[hm]
        fill = (np.repeat(e_st - np.cumsum(e_len) + e_len, e_len)
                + np.arange(int(e_len.sum())))
        out[fill] = 255
        out[e_st + e_len - 1] = (vm[hm] % 255).astype(np.uint8)
    final = bytearray(out.tobytes())
    fin_ls = int(mp[-1] + ml[-1])
    fin_ll = n - fin_ls
    ftl = min(fin_ll, 15)
    final.append(ftl << 4)
    if ftl == 15:
        final += _ext_len(fin_ll - 15)
    final += buf[fin_ls:n]
    return bytes(final)


def _lz_compress_np(data: bytes, prefix: bytes = b"") -> bytes:
    """Vectorized greedy parse: hashed head-table candidates + batched
    8-byte-gram extension + jump-table selection + fused sequence emit."""
    plen = len(prefix)
    buf = prefix + data if plen else data
    n = len(buf)
    if n == plen:
        return b""
    if n - _MIN_MATCH < plen:
        return _only_literals(buf, plen, n)
    ok, cand, mlen = _candidates_np(buf, plen, n)
    return _select_emit(buf, plen, n, ok, cand, mlen)


def _lz_compress_device(data: bytes, prefix: bytes = b"") -> bytes:
    """Device greedy parse: the candidate stage (gram/hash build,
    head-table scatter, batched extension) runs as Pallas kernels + XLA
    scatter via ``repro.kernels.lz_match``; selection/emit is the same
    host code as the NumPy path, so the emitted stream is byte-identical
    to ``_lz_compress_np`` (asserted across the parity corpus in
    tests/test_kernel_codec.py)."""
    from repro.kernels.lz_match import lz_candidates_device

    plen = len(prefix)
    buf = prefix + data if plen else data
    n = len(buf)
    if n == plen:
        return b""
    if n - _MIN_MATCH < plen:
        return _only_literals(buf, plen, n)
    ok, cand, mlen = lz_candidates_device(buf, plen)
    return _select_emit(buf, plen, n, ok, cand, mlen)


def _lz_decompress_np(comp: bytes, prefix: bytes = b"") -> bytes:
    """Vectorized decode.

    Three passes: (1) a speculative parse computes, for *every* byte
    position, the sequence fields a sequence starting there would have
    (literal length incl. ext runs, match length, next-sequence offset) —
    all clamped gathers, no branches; (2) a tiny pointer-chase walks the
    real sequence chain through the precomputed next-array; (3) output is
    built with one bulk gather for all literals and frontier-batched match
    application: each round applies, in a single gather, every match whose
    source no longer intersects any unapplied destination (self-overlapping
    copies fold through ``% offset``).  Dependency chains deeper than
    ``_DECODE_MAX_ROUNDS`` finish on a sequential fallback."""
    n = len(comp)
    if n == 0:
        return b""
    plen = len(prefix)
    c = np.frombuffer(comp, np.uint8)
    pos = np.arange(n, dtype=np.int64)
    ll0 = (c >> 4).astype(np.int64)
    ml0 = (c & 15).astype(np.int64)
    cl = c.astype(np.int64)
    # nn[p]: first q >= p with comp[q] != 255 (n when none) — ext-run ends
    if bool((c == 255).any()):
        nz = np.where(c != 255, pos, np.int64(n))
        nn = np.minimum.accumulate(nz[::-1])[::-1]
    else:
        nn = pos
    npad = np.concatenate([nn, [np.int64(n)]])
    cpad = np.concatenate([cl, [np.int64(0)]])

    def ext_value(start):
        """255-run value beginning at comp[start] (start may be >= n: bad).
        Returns (value, n_ext_bytes, bad)."""
        e = npad[np.minimum(start, n)]
        bad = e >= n
        ec = np.minimum(e, n - 1)
        return 255 * (ec - start) + cpad[ec], ec - start + 1, bad

    has_lext = ll0 == 15
    lv, lc, lbad = ext_value(pos + 1)
    ll = ll0 + np.where(has_lext, lv, 0)
    extl = np.where(has_lext, lc, 0)
    bad = has_lext & lbad
    le = pos + 1 + extl          # literal run start
    q1 = le + ll                 # offset field position
    terminal = q1 == n
    bad |= q1 > n
    bad |= ~terminal & (q1 + 2 > n)
    if n >= 2:
        ov = np.ndarray(shape=(n - 1,), dtype="<u2", buffer=comp, strides=(1,))
        off = ov[np.minimum(q1, n - 2)].astype(np.int64)
    else:
        off = np.zeros(n, np.int64)  # single-byte stream: terminal only
    has_mext = ml0 == 15
    mv_, mc, mbad = ext_value(q1 + 2)
    ml = ml0 + _MIN_MATCH + np.where(has_mext, mv_, 0)
    bad |= has_mext & ~terminal & mbad
    nxt = q1 + 2 + np.where(has_mext, mc, 0)

    # chase the real sequence chain
    nxt_a = array("q")
    nxt_a.frombytes(nxt.tobytes())
    bad_b = bad.tobytes()
    term_b = terminal.tobytes()
    tpos: list = []
    ap = tpos.append
    p = 0
    fin = -1
    while p < n:
        if bad_b[p]:
            raise ValueError("corrupt LZ stream: truncated")
        if term_b[p]:
            fin = p
            break
        ap(p)
        p = nxt_a[p]
    if fin < 0:
        # a valid block always ends with a literals-only sequence (the
        # encoder emits one even when empty); stopping right after a match
        # means the tail was cut off
        raise ValueError("corrupt LZ stream: truncated")
    fin_ll = int(ll[fin])
    fin_ls = int(le[fin])

    S = len(tpos)
    if S == 0:
        out = bytearray(comp[fin_ls : fin_ls + fin_ll])
        return bytes(out)
    tp = np.array(tpos, np.int64)
    ll_v = ll[tp]
    ml_v = ml[tp]
    le_v = le[tp]
    off_v = off[tp]
    if (off_v == 0).any():
        raise ValueError("corrupt LZ stream: zero offset")
    lit_dst = np.empty(S, np.int64)
    lit_dst[0] = plen
    np.cumsum((ll_v + ml_v)[:-1], out=lit_dst[1:])
    lit_dst[1:] += plen
    m_dst = lit_dst + ll_v
    src = m_dst - off_v
    if (src < 0).any():
        raise ValueError("corrupt LZ stream: offset before start")
    total = int(m_dst[-1] + ml_v[-1]) + fin_ll
    out = np.empty(total, np.uint8)
    if plen:
        out[:plen] = np.frombuffer(prefix, np.uint8)
    # literals: one gather/scatter over every span
    if int(ll_v.sum()):
        nz2 = np.flatnonzero(ll_v)
        lln = ll_v[nz2]
        csum = np.cumsum(lln)
        ar = np.arange(int(csum[-1]))
        out[np.repeat(lit_dst[nz2] - csum + lln, lln) + ar] = \
            c[np.repeat(le_v[nz2] - csum + lln, lln) + ar]
    if fin_ll:
        out[total - fin_ll :] = c[fin_ls : fin_ls + fin_ll]
    # matches: sequential application over C arrays + memoryview slice
    # copies.  (A frontier-batched gather scheme was tried and loses: on
    # match-dense prompt corpora the output is one deep copy-chain, so
    # rounds never free more than a handful of matches.)
    d_a = array("q"); d_a.frombytes(m_dst.tobytes())
    s_a = array("q"); s_a.frombytes(src.tobytes())
    m_a = array("q"); m_a.frombytes(ml_v.tobytes())
    o_a = array("q"); o_a.frombytes(off_v.tobytes())
    mv2 = memoryview(out)
    for k in range(S):
        d = d_a[k]
        s = s_a[k]
        m = m_a[k]
        if d - s >= m:
            mv2[d : d + m] = mv2[s : s + m]
        else:
            o = o_a[k]
            seg = bytes(mv2[s : s + o])
            mv2[d : d + m] = (seg * (m // o + 1))[:m]
    return out[plen:].tobytes()


# ---------------------------------------------------------------------------
# Public entry points (size/mode routing)
# ---------------------------------------------------------------------------


def lz_compress(data: bytes, prefix: bytes = b"") -> bytes:
    """Compress ``data`` (optionally against a dictionary ``prefix``).

    Auto-routes scalar vs vectorized on payload size; run-dominated
    payloads (zero pages, padding) stay scalar, where the skip-ahead
    loop is faster than any per-position vectorized scan.
    """
    from repro.core import device as _device

    mode = _lz_mode()
    if mode == "device":   # forced: still counted in the dispatch census
        _device.use_device(len(data), "REPRO_LZ_DEVICE_MIN",
                           _DEVICE_MIN_COMPRESS, force=True)
        return _lz_compress_device(data, prefix)
    if mode == "scalar" or (mode == "auto" and len(data) < _NP_MIN_COMPRESS):
        return _lz_compress_scalar(data, prefix)
    if mode == "auto":
        probe = np.frombuffer(data[:_RUN_PROBE], np.uint8)
        if probe.size > 16 and float((probe[1:] == probe[:-1]).mean()) > 0.5:
            return _lz_compress_scalar(data, prefix)
        if _device.use_device(len(data), "REPRO_LZ_DEVICE_MIN",
                              _DEVICE_MIN_COMPRESS):
            return _lz_compress_device(data, prefix)
    return _lz_compress_np(data, prefix)


def lz_decompress(comp: bytes, prefix: bytes = b"") -> bytes:
    """Decode a block.  ``auto`` stays on the scalar loop: its bulk slice
    copies already run at memcpy speed, and the vectorized
    parse+gather path (kept behind ``REPRO_LZ_MODE=vector``) measured at
    parity on match-dense streams and *slower* on literal-heavy ones —
    the decode-side throughput win comes from the rANS stage instead
    (see ARCHITECTURE.md "Vectorized codec path")."""
    if len(comp) == 0:
        return b""
    if _lz_mode() == "vector" and len(comp) >= _NP_MIN_DECOMPRESS:
        return _lz_decompress_np(comp, prefix)
    return _lz_decompress_scalar(comp, prefix)
