"""Batched entropy engine conformance (ISSUE 6).

Every :mod:`repro.core.entropy` engine must be indistinguishable from the
serial numpy oracle — same encoded bytes, same decoded arrays, and the
same ``ValueError`` on the same (lowest-index) broken payload.  That is
the contract that lets the writer, reader, and serving layers pick an
engine purely on speed: TACZ files stay byte-identical and served crops
stay bit-identical no matter which engine produced or consumed them.

Deterministic parametrized cases run everywhere; hypothesis sweeps run
when the optional dep is installed (same guard as test_she_batched).
"""
import os
import tempfile

import numpy as np
import pytest

from repro.core import entropy, huffman, she, sz

ENGINES = ["numpy", "batched", "pallas"]


def _batch(seed, n_payloads, max_codes, spread=40):
    """(codebook, payload list) — shared codebook over mixed-size payloads
    (including empty ones when n_payloads allows)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(0, max_codes + 1, size=n_payloads)
    pool = rng.integers(-spread, spread + 1, size=int(sizes.sum()) + 1)
    cb = huffman.build_codebook(pool)
    splits = np.cumsum(sizes)[:-1]
    return cb, [p.astype(np.int64) for p in np.split(pool[:-1], splits)]


def _outcome(fn, *args, **kw):
    """Result-or-error fingerprint, comparable across engines."""
    try:
        return ("ok", fn(*args, **kw))
    except ValueError as exc:
        return ("err", str(exc))


def _assert_same_outcome(a, b):
    assert a[0] == b[0], (a, b)
    if a[0] == "err":
        assert a[1] == b[1]
    else:
        for x, y in zip(a[1], b[1]):
            if isinstance(x, tuple):
                assert x == y
            else:
                np.testing.assert_array_equal(x, y)


# ------------------------------ registry -----------------------------------


def test_engine_registry():
    for name in ("numpy", "batched", "pallas"):
        eng = entropy.get_engine(name)
        assert eng.name == name
        assert entropy.get_engine(eng) is eng          # instance passthrough
    assert entropy.get_engine("auto").name in ("batched", "pallas")
    with pytest.raises(ValueError, match="unknown entropy engine"):
        entropy.get_engine("cuda")
    entropy.check_engine_name("auto")                  # no jax import needed
    with pytest.raises(ValueError, match="unknown entropy engine"):
        entropy.check_engine_name("cuda")


# --------------------------- encode/decode parity ---------------------------


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("seed,n_payloads,max_codes", [
    (0, 1, 300),       # single payload (below the batch threshold)
    (1, 3, 100),       # still below _MIN_BATCH — serial fallback path
    (2, 12, 200),      # batched path, mixed sizes incl. empty payloads
    (3, 40, 64),       # many small payloads
    (4, 6, 3000),      # payloads spanning several device bit-axis tiles
])
def test_engine_matches_oracle(engine, seed, n_payloads, max_codes):
    cb, codes_list = _batch(seed, n_payloads, max_codes)
    oracle = entropy.get_engine("numpy")
    eng = entropy.get_engine(engine)
    enc_ref = oracle.encode_payloads(cb, codes_list)
    enc = eng.encode_payloads(cb, codes_list)
    assert enc == enc_ref                              # bytes, not just bits
    payloads = [(blob, nbits, c.size)
                for (blob, nbits), c in zip(enc_ref, codes_list)]
    dec = eng.decode_payloads(cb, payloads)
    for out, ref in zip(dec, codes_list):
        assert out.dtype == np.int64
        np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_empty_batch_and_streams(engine):
    eng = entropy.get_engine(engine)
    cb = huffman.build_codebook(np.arange(5))
    assert eng.encode_payloads(cb, []) == []
    assert eng.decode_payloads(cb, []) == []
    enc = eng.encode_payloads(cb, [np.zeros(0, np.int64)] * 6)
    assert enc == [(b"", 0)] * 6
    dec = eng.decode_payloads(cb, [(b"", 0, 0)] * 6)
    assert all(d.size == 0 for d in dec)


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_single_symbol_codebook(engine):
    data = np.full(9, 3, dtype=np.int64)
    cb = huffman.build_codebook(data)
    eng = entropy.get_engine(engine)
    (blob, nbits), = eng.encode_payloads(cb, [data])
    assert nbits == 9
    out, = eng.decode_payloads(cb, [(blob, nbits, 9)])
    np.testing.assert_array_equal(out, data)


# ------------------------------ error parity --------------------------------


@pytest.mark.parametrize("engine", ENGINES[1:])
def test_truncation_error_parity(engine):
    cb, codes_list = _batch(7, 8, 120)
    oracle = entropy.get_engine("numpy")
    eng = entropy.get_engine(engine)
    enc = oracle.encode_payloads(cb, codes_list)
    payloads = [(blob, nbits, c.size)
                for (blob, nbits), c in zip(enc, codes_list)]
    # break one payload several ways; every engine must raise the oracle's
    # exact message (which names the lowest broken payload's failure mode)
    for victim in (0, 3, len(payloads) - 1):
        for cut in (1, 7, 13):
            broken = list(payloads)
            blob, nbits, n = broken[victim]
            if nbits <= cut:
                continue
            broken[victim] = (blob, nbits - cut, n)
            _assert_same_outcome(
                _outcome(oracle.decode_payloads, cb, broken),
                _outcome(eng.decode_payloads, cb, broken))


@pytest.mark.parametrize("engine", ENGINES[1:])
def test_garbage_fuzz_parity(engine):
    """Random buffers/nbits/n_codes: ok-vs-error (and the error text) must
    match the oracle on every payload batch."""
    rng = np.random.default_rng(11)
    cb = huffman.build_codebook(rng.integers(-30, 31, size=4000))
    oracle = entropy.get_engine("numpy")
    eng = entropy.get_engine(engine)
    for _ in range(20):
        batch = []
        for _ in range(int(rng.integers(4, 10))):
            buf = rng.integers(0, 256, size=int(rng.integers(0, 40)),
                               dtype=np.uint8).tobytes()
            nbits = int(rng.integers(0, 8 * max(len(buf), 1) + 8))
            n = int(rng.integers(0, 60))
            batch.append((buf, nbits, n))
        _assert_same_outcome(_outcome(oracle.decode_payloads, cb, batch),
                             _outcome(eng.decode_payloads, cb, batch))


@pytest.mark.parametrize("engine", ENGINES[1:])
def test_incomplete_codebook_corrupt_parity(engine):
    """Only an *incomplete* code (Kraft sum < 1) has a gap the decoder can
    fall into — the one way to hit 'corrupt bitstream' rather than
    'truncated'.  Engines must agree on which it is, case by case."""
    cb = huffman._canonicalize(np.array([1, 2, 3]),
                               np.array([2, 2, 2]))       # gap at code 0b11
    oracle = entropy.get_engine("numpy")
    eng = entropy.get_engine(engine)
    cases = [
        (bytes([0b11000000]), 8, 4),      # lands in the gap → corrupt
        (bytes([0b11000000]), 2, 1),      # gap but stream ends → truncated
        (bytes([0b00011011]), 8, 4),      # valid prefix, then runs out
    ]
    for case in cases:
        batch = [(bytes([0b00011011]), 8, 4), case] * 3   # mixed positions
        _assert_same_outcome(_outcome(oracle.decode_payloads, cb, batch),
                             _outcome(eng.decode_payloads, cb, batch))


@pytest.mark.parametrize("engine", ENGINES)
def test_empty_codebook_raise_parity(engine):
    cb = huffman.build_codebook(np.zeros(0, dtype=np.int64))
    eng = entropy.get_engine(engine)
    out = eng.decode_payloads(cb, [(b"", 0, 0)] * 5)
    assert all(o.size == 0 for o in out)
    with pytest.raises(ValueError, match="empty codebook"):
        eng.decode_payloads(cb, [(b"", 0, 0), (b"\x00", 3, 2)])


# ------------------------- wrapper compatibility ----------------------------


def test_huffman_wrappers_unchanged():
    rng = np.random.default_rng(5)
    data = rng.integers(-50, 51, size=700)
    cb = huffman.build_codebook(data)
    packed, nbits = huffman.encode(cb, data)
    p2, n2 = entropy.encode_stream(cb, data)
    assert nbits == n2 and np.array_equal(packed, p2)
    np.testing.assert_array_equal(huffman.decode(cb, packed, nbits, 700),
                                  entropy.decode_stream(cb, packed, nbits,
                                                        700))


@pytest.mark.parametrize("engine", ENGINES)
def test_she_wrappers_route_engines(engine):
    cb, codes_list = _batch(9, 10, 150)
    enc = she.encode_brick_payloads(cb, codes_list, engine=engine)
    assert enc == she.encode_brick_payloads(cb, codes_list, engine="numpy")
    payloads = [(blob, nbits, c.size)
                for (blob, nbits), c in zip(enc, codes_list)]
    for out, ref in zip(
            she.decode_brick_payloads(cb, payloads, engine=engine),
            codes_list):
        np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("engine", ENGINES)
def test_sz_entropy_stage_engine_param(engine):
    rng = np.random.default_rng(13)
    codes = rng.integers(-20, 21, size=3000)
    ref_bits, ref_cb_bits, ref_art = sz.entropy_stage(codes, engine="numpy")
    bits, cb_bits, art = sz.entropy_stage(codes, engine=engine)
    assert (bits, cb_bits) == (ref_bits, ref_cb_bits)
    assert art["packed"] == ref_art["packed"]
    assert art["nbits"] == ref_art["nbits"]
    np.testing.assert_array_equal(art["codebook"].symbols,
                                  ref_art["codebook"].symbols)


def test_encoded_size_bits_vectorized_regression():
    """`encoded_size_bits` must price exactly what `encode` emits, in both
    call forms, including repeated symbols (the old per-symbol Python loop
    mispriced nothing but was O(n·unique); the vectorized form must keep
    the exact contract)."""
    rng = np.random.default_rng(17)
    data = rng.integers(-9, 10, size=2500)
    cb = huffman.build_codebook(data)
    _, nbits = huffman.encode(cb, data)
    assert huffman.encoded_size_bits(cb, data=data) == nbits
    symbols, freqs = np.unique(data, return_counts=True)
    assert huffman.encoded_size_bits(cb, symbols=symbols,
                                     freqs=freqs) == nbits
    assert huffman.encoded_size_bits(
        cb, symbols=np.zeros(0, np.int64),
        freqs=np.zeros(0, np.int64)) == 0


# ------------------------- end-to-end bit-identity --------------------------


def test_tacz_files_byte_identical_across_engines(tmp_path):
    from repro.io.writer import TACZWriter
    rng = np.random.default_rng(21)
    levels = [rng.normal(size=(24, 24, 24)).astype(np.float32)
              for _ in range(2)]
    blobs = {}
    for engine in ENGINES:
        p = os.path.join(tmp_path, f"{engine}.tacz")
        with TACZWriter(p, eb=1e-3, entropy_engine=engine,
                        lorenzo_engine="numpy") as w:
            for lv in levels:
                w.add_level(lv)
        with open(p, "rb") as f:
            blobs[engine] = f.read()
    assert blobs["batched"] == blobs["numpy"]
    assert blobs["pallas"] == blobs["numpy"]


def test_reader_and_server_identical_across_engines(tmp_path):
    from repro.io.reader import TACZReader
    from repro.serving.regions import RegionServer
    rng = np.random.default_rng(23)
    level = rng.normal(size=(32, 32, 32)).astype(np.float32)
    from repro.io.writer import TACZWriter
    p = os.path.join(tmp_path, "snap.tacz")
    with TACZWriter(p, eb=1e-3, lorenzo_engine="numpy") as w:
        w.add_level(level)
    ref_rd = TACZReader(p, entropy_engine="numpy")
    ref = ref_rd.read_level(0)
    box = ((3, 29), (5, 27), (0, 32))
    ref_roi = ref_rd.read_roi(box)
    for engine in ENGINES[1:]:
        rd = TACZReader(p, entropy_engine=engine)
        np.testing.assert_array_equal(rd.read_level(0), ref)
        for a, b in zip(rd.read_roi(box), ref_roi):
            np.testing.assert_array_equal(a.data, b.data)
        # batched decode surface == serial per-payload surface
        n = len(rd.levels[0].subblocks)
        dec = rd.decode_subblocks(0, list(range(n)))
        for sbi in range(n):
            c, b = ref_rd.subblock_codes(0, sbi)
            np.testing.assert_array_equal(dec[sbi][0], c)
            if b is None:
                assert dec[sbi][1] is None
            else:
                np.testing.assert_array_equal(dec[sbi][1], b)
        rd.close()
        with RegionServer(p, entropy_engine=engine) as srv, \
                RegionServer(p, entropy_engine="numpy") as srv_ref:
            for la, lb in zip(srv.get_roi(box), srv_ref.get_roi(box)):
                np.testing.assert_array_equal(la.data, lb.data)
    ref_rd.close()


def test_multipart_decode_subblocks_across_parts(tmp_path):
    from repro.io.parallel import MultiPartReader, write_multipart
    rng = np.random.default_rng(29)
    from repro.core.amr import synthetic_amr
    ds = synthetic_amr((32, 32, 32), densities=[0.5, 0.5], refine_block=4,
                       seed=3)
    d = os.path.join(tmp_path, "snap")
    write_multipart(d, ds, parts=3, eb=1e-3, lorenzo_engine="numpy")
    with MultiPartReader(d, entropy_engine="batched") as rd, \
            MultiPartReader(d, entropy_engine="numpy") as ref:
        for li in range(len(rd.levels)):
            n = len(rd.levels[li].subblocks)
            if not n:
                continue
            sbis = list(range(n))[::-1]          # arbitrary order
            dec = rd.decode_subblocks(li, sbis)
            for pos, sbi in enumerate(sbis):
                c, b = ref.subblock_codes(li, sbi)
                np.testing.assert_array_equal(dec[pos][0], c)
                if b is not None:
                    np.testing.assert_array_equal(dec[pos][1], b)
            np.testing.assert_array_equal(rd.read_level(li),
                                          ref.read_level(li))


# ------------------------- device/host accounting ---------------------------


def _counter(family, *labels):
    child = family.children().get(tuple(labels))
    return 0.0 if child is None else child.value


def test_window_budget_fallback_counted(monkeypatch):
    """A payload too long for a device launch of its own is decoded on
    the host and counted under ``window_budget``; the rest still run on
    the device in several launches, and every output matches the oracle."""
    from repro.obs import metrics as obsm
    rng = np.random.default_rng(31)
    cb = huffman.build_codebook(rng.integers(-20, 21, size=5000))
    sizes = [40, 60, 80, 100, 120, 2000]
    codes_list = [rng.integers(-20, 21, size=n) for n in sizes]
    enc = entropy.get_engine("numpy").encode_payloads(cb, codes_list)
    payloads = [(blob, nbits, c.size)
                for (blob, nbits), c in zip(enc, codes_list)]
    eng = entropy.PallasEngine()
    longest = max(nbits for _, nbits, _ in payloads)
    # the longest payload alone overflows; two short ones fit per launch
    budget = eng._launch_elems(2, sorted(p[1] for p in payloads)[-2])
    assert eng._launch_elems(1, longest) > budget
    monkeypatch.setattr(entropy, "_MAX_PALLAS_WINDOW_ELEMS", budget)
    host = (obsm.HOST_FALLBACKS, "huffman_decode", "window_budget")
    dev = (obsm.DEVICE_ITEMS, "huffman_decode", "cpu:0")
    h0, d0 = _counter(*host), _counter(*dev)
    for out, ref in zip(eng.decode_payloads(cb, payloads), codes_list):
        np.testing.assert_array_equal(out, ref)
    assert _counter(*host) - h0 == 1
    assert _counter(*dev) - d0 == len(sizes) - 1


# --------------------------- hypothesis sweeps ------------------------------
#
# Guarded (not importorskip'd at module level) so the deterministic cases
# above still run in environments without the optional hypothesis dep.

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
except ImportError:        # pragma: no cover - environment dependent
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 10_000), n_payloads=st.integers(1, 24),
           max_codes=st.integers(0, 120), spread=st.integers(0, 300),
           engine=st.sampled_from(ENGINES[1:]))
    def test_property_engines_match_oracle(seed, n_payloads, max_codes,
                                           spread, engine):
        cb, codes_list = _batch(seed, n_payloads, max_codes, spread)
        oracle = entropy.get_engine("numpy")
        eng = entropy.get_engine(engine)
        enc = oracle.encode_payloads(cb, codes_list)
        assert eng.encode_payloads(cb, codes_list) == enc
        payloads = [(blob, nbits, c.size)
                    for (blob, nbits), c in zip(enc, codes_list)]
        for out, ref in zip(eng.decode_payloads(cb, payloads), codes_list):
            np.testing.assert_array_equal(out, ref)

    @given(seed=st.integers(0, 10_000), victim=st.integers(0, 7),
           cut=st.integers(1, 40), engine=st.sampled_from(ENGINES[1:]))
    def test_property_truncation_parity(seed, victim, cut, engine):
        cb, codes_list = _batch(seed, 8, 80)
        oracle = entropy.get_engine("numpy")
        enc = oracle.encode_payloads(cb, codes_list)
        payloads = [(blob, nbits, c.size)
                    for (blob, nbits), c in zip(enc, codes_list)]
        blob, nbits, n = payloads[victim]
        payloads[victim] = (blob, max(nbits - cut, 0), n)
        _assert_same_outcome(
            _outcome(oracle.decode_payloads, cb, payloads),
            _outcome(entropy.get_engine(engine).decode_payloads,
                     cb, payloads))
