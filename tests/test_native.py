"""Native host runtime: build, ring round-trip, threaded stress, interner
semantics — and the pure-Python fallback path."""

import threading

import numpy as np
import pytest

from sentinel_tpu.native import EventRing, NativeInterner, native_available


def test_native_builds():
    # g++ is in the image; the native path must actually come up
    assert native_available()


@pytest.mark.parametrize("force_fallback", [False, True])
def test_ring_roundtrip(force_fallback, monkeypatch):
    if force_fallback:
        import sentinel_tpu.native.ring as RM

        monkeypatch.setattr(RM, "load_native", lambda: None)
    r = EventRing(1 << 8)
    assert r.native is not force_fallback
    for i in range(10):
        assert r.push(res=i, count=i + 1, rt_ms=float(i) / 2, user_tag=100 + i)
    assert len(r) == 10
    res, count, origin, ph, flags, rt, err, tag, aux0, aux1, aux2, aux3 = r.drain(64)
    assert list(res) == list(range(10))
    assert list(count) == [i + 1 for i in range(10)]
    np.testing.assert_allclose(rt, [i / 2 for i in range(10)])
    assert list(tag) == [100 + i for i in range(10)]
    assert len(r) == 0


def test_ring_full_and_wraparound():
    r = EventRing(1 << 4)
    for i in range(16):
        assert r.push(res=i)
    assert not r.push(res=99)  # full
    out = r.drain(8)
    assert list(out[0]) == list(range(8))
    for i in range(8):  # wrap
        assert r.push(res=100 + i)
    out = r.drain(32)
    assert list(out[0]) == list(range(8, 16)) + [100 + i for i in range(8)]


def test_ring_threaded_stress():
    r = EventRing(1 << 12)
    n_threads, per_thread = 8, 2000
    drained = []
    stop = threading.Event()

    def producer(t):
        pushed = 0
        while pushed < per_thread:
            if r.push(res=t * per_thread + pushed):
                pushed += 1

    def consumer():
        while not stop.is_set() or len(r):
            out = r.drain(512)
            if len(out[0]):
                drained.append(np.array(out[0]))

    ct = threading.Thread(target=consumer)
    ct.start()
    threads = [threading.Thread(target=producer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    ct.join()
    got = np.concatenate(drained) if drained else np.array([])
    assert len(got) == n_threads * per_thread
    # every event delivered exactly once
    assert len(np.unique(got)) == len(got)


def test_completion_overflow_never_drops(client, vt):
    """A full ring spills to the overflow list; nothing is lost (losses
    would leak engine concurrency forever)."""
    import sentinel_tpu as st

    client.flow_rules.load([st.FlowRule(resource="ovf", count=1000)])
    client._comp_ring = EventRing(1 << 2)  # tiny ring: 4 slots
    entries = [client.entry("ovf") for _ in range(10)]  # sync: ticks run
    mode = client.mode
    client.mode = "threaded"  # hold ticks while we queue exits
    for e in entries:
        vt.advance(1)
        e.exit()
    assert len(client._comp_overflow) == 10 - (1 << 2)
    client.mode = mode
    client.tick_once()
    s = client.stats.resource("ovf")
    assert s["successQps"] == 10  # every completion landed
    assert s["curThreadNum"] == 0  # concurrency fully released
    assert not client._comp_overflow


def test_interner_dense_ids_and_capacity():
    t = NativeInterner(1 << 8, first_id=5, max_ids=5 + 3)
    assert t.native
    a = t.get("alpha")
    b = t.get("beta")
    assert (a, b) == (5, 6)
    assert t.get("alpha") == 5  # stable
    assert t.get("gamma") == 7
    assert t.get("delta") == -1  # id space exhausted
    assert t.count() == 3


def test_interner_threaded_consistency():
    t = NativeInterner(1 << 12, first_id=0, max_ids=10000)
    names = [f"res-{i % 50}" for i in range(2000)]
    results = {}
    lock = threading.Lock()

    def worker(offset):
        local = {}
        for n in names[offset::4]:
            local[n] = t.get(n)
        with lock:
            for k, v in local.items():
                assert results.setdefault(k, v) == v  # same id everywhere

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(results) == 50
    assert sorted(results.values()) == list(range(50))


def test_batch_sort_native_matches_numpy_fallback(monkeypatch):
    """The C stable argsort (sx_batch_sort5/3) must be byte-identical to
    the np.lexsort fallback — order AND inverse permutation, ties
    included (both sides are stable sorts over the same key order)."""
    import sentinel_tpu.native.ring as RM

    assert native_available()  # the native path must actually be on trial
    rng = np.random.default_rng(7)
    for n in (0, 1, 3, 257, 20000):
        # tiny key ranges force heavy ties — the stability trap
        k5 = [rng.integers(-2, 3, n).astype(np.int32) for _ in range(5)]
        k3 = [rng.integers(0, 4, n).astype(np.int32) for _ in range(3)]
        o5n, i5n = RM.batch_sort5(*k5)
        o3n, i3n = RM.batch_sort3(*k3, want_inv=True)
        with monkeypatch.context() as m:
            m.setattr(RM, "load_native", lambda: None)
            o5f, i5f = RM.batch_sort5(*k5)
            o3f, i3f = RM.batch_sort3(*k3, want_inv=True)
        assert np.array_equal(o5n, o5f) and np.array_equal(i5n, i5f)
        assert np.array_equal(o3n, o3f) and np.array_equal(i3n, i3f)
        # both agree with the reference np.lexsort key order
        assert np.array_equal(o5f, np.lexsort((k5[4], k5[3], k5[2], k5[1], k5[0])))
        if n:
            assert np.array_equal(i5n[o5n], np.arange(n))


# -- the tick builder's presort (native/ring.presort) ------------------------
#
# The reference is the parent's presort, kept here in its few lines: a stable
# lexsort of ALL B rows, the inverse by scatter, one np.take a column.  The
# new routine sorts the live rows only and must give the same bits.

_NODE_ROWS = 16368  # the served deployments' tables; trash row = the last
_TRASH = _NODE_ROWS - 1
_SMALL_N = 1024  # SX_PRESORT_SMALL_N: std::sort at or under it, radix over


def _reference_presort(keys, cols, wide):
    order = np.lexsort(tuple(reversed(keys))).astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(order.shape[0], dtype=np.int32)
    return order, inv, [np.take(x, order) for x in cols], np.take(wide, order, axis=0)


def _presort_keys(kind, rng, B, n):
    """Five B-row key columns, the first n live, the rest one padding run."""
    if kind == "served":
        # res-major keys as _run_tick builds them: ruled ids, sketch-tier
        # ids beyond 2**20 + node_rows (they sort AFTER the padding run),
        # live rows on the trash row (the negative-id sanitiser) and live
        # rows whose whole key equals the padding key
        pad = (_TRASH, _TRASH, _TRASH, -1, -1)
        z = rng.zipf(1.3, n) % (1 << 20)
        res = np.where(z <= 10_000, z, _NODE_ROWS + z).astype(np.int32)
        res[rng.random(n) < 0.02] = _TRASH
        res[rng.random(n) < 0.01] = (1 << 20) + _NODE_ROWS
        with_origin = rng.random(n) < 0.125
        live = [
            res,
            np.where(rng.random(n) < 0.5, 7, _TRASH),
            np.where(with_origin, 9, _TRASH),
            np.where(with_origin, 3, -1),
            np.where(rng.random(n) < 0.1, rng.integers(0, 40, n), -1),
        ]
    elif kind == "ties":
        pad = (0, 1, -1, 2, -2)
        live = [rng.integers(-2, 3, n) for _ in range(5)]
    else:  # "wide": five full-range keys, 160 bits: several packed rounds
        pad = (-5, 2**31 - 1, -(2**31), 0, 17)
        live = [rng.integers(-(2**31), 2**31, n) for _ in range(5)]
        if n:
            live[0][rng.random(n) < 0.5] = pad[0]  # deeper keys decide
    keys = []
    for fill, k in zip(pad, live):
        col = np.full(B, fill, np.int32)
        col[:n] = k
        keys.append(col)
    return keys


_PRESORT_SHAPES = [(256, n) for n in (0, 1, 2, 255, 256)] + [
    (131072, n)
    for n in (0, 1, 2, 255, 256, 257, _SMALL_N, _SMALL_N + 1, 4096, 43000, 131072)
]


@pytest.mark.parametrize("force_fallback", [False, True], ids=["native", "numpy"])
@pytest.mark.parametrize("kind,m", [("served", 2), ("ties", 1), ("wide", 3)])
@pytest.mark.parametrize("B,n", _PRESORT_SHAPES)
def test_presort_matches_lexsort_and_take(B, n, kind, m, force_fallback, monkeypatch):
    """order, inv and every permuted column equal np.lexsort + np.take over
    the B-row columns bit for bit, native and fallback, on both sides of the
    small-n threshold, with the padding run in the middle of the order."""
    import sentinel_tpu.native.ring as RM

    assert native_available()
    rng = np.random.default_rng(B + 7 * n + len(kind))
    keys = _presort_keys(kind, rng, B, n)
    cols = keys + [rng.integers(-9, 9, B).astype(np.int32) for _ in range(3)]
    cols.append(rng.random(B).astype(np.float32))  # a 4-byte column that is no int
    wide = rng.integers(0, 1 << 20, (B, m)).astype(np.int32)
    for x in cols[5:] + [wide]:
        x[n:] = 0  # padding rows hold one fill value a column
    want_order, want_inv, want_cols, want_wide = _reference_presort(keys, cols, wide)

    order, inv = np.empty(B, np.int32), np.empty(B, np.int32)
    dst = [np.empty_like(x) for x in cols]
    wide_dst = np.empty(wide.shape[::-1], np.int32)  # lane by lane
    if force_fallback:
        monkeypatch.setattr(RM, "load_native", lambda: None)
    path = RM.presort(
        keys, n, order, inv, np.empty(2 * B, np.uint64), cols, dst, wide, wide_dst
    )
    assert path == ("numpy" if force_fallback else "small" if n <= _SMALL_N else "radix")
    assert order.tobytes() == want_order.tobytes()
    assert inv.tobytes() == want_inv.tobytes()
    for got, want in zip(dst, want_cols):
        assert got.tobytes() == want.tobytes()
    assert wide_dst.tobytes() == np.ascontiguousarray(want_wide.T).tobytes()
    if kind == "served" and 255 <= n < B:
        # the run of padding rows sits inside the order, not at its end
        at = int(inv[n])
        assert list(order[at : at + B - n]) == list(range(n, B))
        assert 0 < at < n


@pytest.mark.parametrize("force_fallback", [False, True], ids=["native", "numpy"])
@pytest.mark.parametrize("n", [2, 255, _SMALL_N, _SMALL_N + 1, 43000])
def test_presort_three_key_completion_form(n, force_fallback, monkeypatch):
    """The completion side: three keys, every row live (the columns are not
    padded before the sort), no inverse wanted, float and aux columns."""
    import sentinel_tpu.native.ring as RM

    rng = np.random.default_rng(n)
    res = (rng.zipf(1.3, n) % 10_000).astype(np.int32)
    ctx = np.where(rng.random(n) < 0.5, 7, _TRASH).astype(np.int32)
    org = np.where(rng.random(n) < 0.125, 9, _TRASH).astype(np.int32)
    cols = [res, ctx, org, rng.random(n).astype(np.float32),
            rng.integers(0, 1 << 20, n).astype(np.int32)]
    want = np.lexsort((org, ctx, res))
    order = np.empty(n, np.int32)
    dst = [np.empty_like(x) for x in cols]
    if force_fallback:
        monkeypatch.setattr(RM, "load_native", lambda: None)
    RM.presort((res, ctx, org), n, order, None, np.empty(2 * n, np.uint64), cols, dst)
    assert np.array_equal(order, want)
    for got, x in zip(dst, cols):
        assert got.tobytes() == x[want].tobytes()


def test_presort_refuses_columns_it_cannot_permute():
    """Pointers go to native code only after sizes, dtypes and contiguity
    were checked here."""
    import sentinel_tpu.native.ring as RM

    k = np.zeros(8, np.int32)
    order, scratch = np.empty(8, np.int32), np.empty(16, np.uint64)
    ok = dict(order=order, inv=None, scratch=scratch)
    with pytest.raises(ValueError):
        RM.presort((k.astype(np.int64),), 8, **ok)  # keys are int32
    with pytest.raises(ValueError):
        RM.presort((k,), 9, **ok)  # more live rows than rows
    with pytest.raises(ValueError):
        RM.presort((k[:4],), 8, **ok)  # a key column shorter than B
    with pytest.raises(ValueError):
        RM.presort((k,), 8, order, None, scratch[:8])  # scratch holds 2 n
    with pytest.raises(ValueError):
        RM.presort((k,), 8, src=(k.astype(np.int64),), dst=(k.copy(),), **ok)
    with pytest.raises(ValueError):
        RM.presort((k,), 8, src=(np.zeros(16, np.int32)[::2],), dst=(k.copy(),), **ok)
    with pytest.raises(ValueError):
        RM.presort((k,), 8, src=(k,), dst=(), **ok)
    assert RM.presort((k,), 8, src=(k,), dst=(k.copy(),), **ok) == "small"


def test_batch_framing_native_matches_numpy_fallback(monkeypatch):
    """Protocol-v2 frame pack/unpack (sx_frame_pack_entries & co) must be
    BYTE-identical to the numpy big-endian structured fallback — the two
    ends of one connection may be built differently."""
    import sentinel_tpu.native.ring as RM

    assert native_available()
    rng = np.random.default_rng(13)
    for n in (0, 1, 5, 2048):
        kinds = rng.integers(0, 255, n).astype(np.uint8)
        ids = rng.integers(-(2**62), 2**62, n).astype(np.int64)
        counts = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
        flags = rng.integers(0, 255, n).astype(np.uint8)
        statuses = rng.integers(-128, 127, n).astype(np.int8)
        waits = rng.integers(0, 2**31 - 1, n).astype(np.int32)
        wire_e_n = RM.pack_batch_entries(kinds, ids, counts, flags)
        wire_r_n = RM.pack_batch_results(statuses, counts, waits, ids)
        cols_e_n = RM.unpack_batch_entries(wire_e_n)
        cols_r_n = RM.unpack_batch_results(wire_r_n)
        with monkeypatch.context() as m:
            m.setattr(RM, "load_native", lambda: None)
            assert RM.pack_batch_entries(kinds, ids, counts, flags) == wire_e_n
            assert RM.pack_batch_results(statuses, counts, waits, ids) == wire_r_n
            cols_e_f = RM.unpack_batch_entries(wire_e_n)
            cols_r_f = RM.unpack_batch_results(wire_r_n)
        for a, b in zip(cols_e_n, cols_e_f):
            assert np.array_equal(a, b)
        for a, b in zip(cols_r_n, cols_r_f):
            assert np.array_equal(a, b)
        # round-trip restores the original columns exactly
        for a, b in zip(cols_e_n, (kinds, ids, counts, flags)):
            assert np.array_equal(a, b)
        for a, b in zip(cols_r_n, (statuses, counts, waits, ids)):
            assert np.array_equal(a, b)
    # a length that is not a whole number of entries is rejected on BOTH paths
    wire = RM.pack_batch_entries(*(np.zeros(2, dt) for dt in
                                   (np.uint8, np.int64, np.int32, np.uint8)))
    for use_fallback in (False, True):
        with monkeypatch.context() as m:
            if use_fallback:
                m.setattr(RM, "load_native", lambda: None)
            with pytest.raises(ValueError):
                RM.unpack_batch_entries(wire[:-1])
            with pytest.raises(ValueError):
                RM.unpack_batch_results(wire)  # 28 bytes is not k × 17
