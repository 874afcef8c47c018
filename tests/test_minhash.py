"""MinHash survivor-sketch (spec v2) properties: determinism, container
independence, shift robustness, similarity monotonicity, and
Jaccard-estimate accuracy vs the exact set computation."""

import numpy as np

from fastdfs_tpu.ops import minhash as M


def _sig(data: bytes, perms=64, k=5):
    arr = np.frombuffer(data, dtype=np.uint8)
    batch = arr[None, :]
    return np.asarray(M.minhash_batch(batch, np.array([len(data)]), perms, k))[0]


def _exact_jaccard(a: bytes, b: bytes, k=5):
    sa = {a[i:i + k] for i in range(len(a) - k + 1)}
    sb = {b[i:i + k] for i in range(len(b) - k + 1)}
    return len(sa & sb) / len(sa | sb)


def test_identical_data_identical_signature():
    rng = np.random.RandomState(1)
    data = rng.randint(0, 256, size=16384, dtype=np.uint8).tobytes()
    assert np.array_equal(_sig(data), _sig(data))


def test_container_length_does_not_change_sketch():
    # z is defined on word_index mod NUM_SEGMENTS, so the same content in
    # a larger zero-padded container yields the identical survivor vector.
    rng = np.random.RandomState(9)
    data = rng.randint(0, 256, size=10000, dtype=np.uint8)
    lens = np.array([10000], dtype=np.int32)
    small = np.zeros((1, 12288), dtype=np.uint8)
    small[0, :10000] = data
    big = np.zeros((1, 65536), dtype=np.uint8)
    big[0, :10000] = data
    za = np.asarray(M.survivor_segmin(small, lens))
    zb = np.asarray(M.survivor_segmin(big, lens))
    assert np.array_equal(za, zb)


def test_shifted_content_mostly_agrees():
    # Survivor sampling is keyed on hash VALUES, so rotating the content
    # keeps (almost) the same survivor set; only segment-collision
    # thinning (position-dependent, ~10% at this density) differs.
    rng = np.random.RandomState(2)
    base = rng.randint(0, 256, size=65536, dtype=np.uint8).tobytes()
    rot = base[10:] + base[:10]
    sim = float(np.mean(_sig(base) == _sig(rot)))
    assert sim > 0.6, sim


def test_similar_vs_dissimilar():
    rng = np.random.RandomState(2)
    base = rng.randint(0, 256, size=16384, dtype=np.uint8)
    near = base.copy()
    near[100:110] = rng.randint(0, 256, size=10, dtype=np.uint8)  # tiny edit
    far = rng.randint(0, 256, size=16384, dtype=np.uint8)

    sim_near = float(np.mean(_sig(base.tobytes()) == _sig(near.tobytes())))
    sim_far = float(np.mean(_sig(base.tobytes()) == _sig(far.tobytes())))
    assert sim_near > 0.85, sim_near
    assert sim_far < 0.2, sim_far


def test_jaccard_estimate_tracks_exact():
    rng = np.random.RandomState(3)
    base = rng.randint(0, 256, size=32768, dtype=np.uint8)
    for frac in (0.0, 0.25, 0.5):
        other = base.copy()
        n_edit = int(len(base) * frac)
        if n_edit:
            other[:n_edit] = rng.randint(0, 256, size=n_edit, dtype=np.uint8)
        exact = _exact_jaccard(base.tobytes(), other.tobytes())
        est = float(np.mean(_sig(base.tobytes(), perms=256) ==
                            _sig(other.tobytes(), perms=256)))
        assert abs(est - exact) < 0.12, (frac, exact, est)


def test_batch_matches_single():
    rng = np.random.RandomState(4)
    chunks = [rng.randint(0, 256, size=n, dtype=np.uint8).tobytes()
              for n in (100, 2000, 4096)]
    L = max(len(c) for c in chunks)
    batch = np.zeros((len(chunks), L), dtype=np.uint8)
    lens = np.array([len(c) for c in chunks], dtype=np.int32)
    for i, c in enumerate(chunks):
        batch[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
    sigs = np.asarray(M.minhash_batch(batch, lens))
    for i, c in enumerate(chunks):
        assert np.array_equal(sigs[i], _sig(c))


def test_padding_does_not_leak_into_signature():
    data = b"hello world, hello dedup" * 50
    arr = np.frombuffer(data, dtype=np.uint8)
    a = np.asarray(M.minhash_batch(arr[None, :], np.array([len(data)])))[0]
    padded = np.zeros((1, len(data) + 512), dtype=np.uint8)
    padded[0, : len(data)] = arr
    b = np.asarray(M.minhash_batch(padded, np.array([len(data)])))[0]
    assert np.array_equal(a, b)


def test_tiny_chunks_do_not_crash():
    for n in (1, 3, 4, 5):
        data = bytes(range(n))
        sig = _sig(data)
        assert sig.shape == (64,)


def test_empty_signature_is_neutral_in_file_level_min():
    # A no-survivor chunk signs all-EMPTY, which must not perturb the
    # file-level signature (elementwise min over chunk signatures).
    rng = np.random.RandomState(6)
    data = rng.randint(0, 256, size=(1, 16384), dtype=np.uint8)
    lens = np.array([16384], dtype=np.int32)
    sig = np.asarray(M.minhash_batch(data, lens))[0]
    empty = np.full_like(sig, M.EMPTY)
    assert np.array_equal(np.minimum(sig, empty), sig)


def test_estimate_jaccard_shape():
    a = np.zeros((3, 64), dtype=np.uint32)
    b = np.zeros((3, 64), dtype=np.uint32)
    out = np.asarray(M.estimate_jaccard(a, b))
    assert out.shape == (3,) and np.all(out == 1.0)


# ---------------------------------------------------------------------------
# recall referee: retrieval against ground truth, and against a witness
# ---------------------------------------------------------------------------

def _crawl(n_base=8, n_docs=32, L=64 << 10, seed=4):
    """Base pages, near-duplicate variants and bait.  truth[i] is the base
    a variant must retrieve (-1: not a query).  Variants are a base with
    0.5% of its bytes rewritten, in 16-byte spans or as scattered single
    bytes (each damages ``shingle`` shingles: the worst case a byte);
    distractors are a base's tokens reshuffled: the same vocabulary and
    almost no shared 5-grams, never a right answer."""
    import random
    rng, nprng = random.Random(seed), np.random.RandomState(seed)
    words = [f"tok{j}" for j in range(8000)]
    docs = np.zeros((n_docs, L), dtype=np.uint8)
    truth = np.full(n_docs, -1)
    for b in range(n_base):
        body = " ".join(rng.choices(words, k=L // 8))
        docs[b] = np.frombuffer(
            (f"<html><body>{body}</body></html>".encode() + b" " * L)[:L],
            dtype=np.uint8)
    for i in range(n_base, n_docs):
        b, kind = rng.randrange(n_base), rng.random()
        row = docs[b].copy()
        if kind < 0.4:
            for _ in range(L // (200 * 16)):
                at = nprng.randint(0, L - 16)
                row[at:at + 16] = nprng.randint(97, 123, 16, dtype=np.uint8)
            truth[i] = b
        elif kind < 0.8:
            at = nprng.choice(L, size=L // 200, replace=False)
            row[at] = nprng.randint(97, 123, len(at), dtype=np.uint8)
            truth[i] = b
        else:
            toks = bytes(row).split(b" ")
            rng.shuffle(toks)
            row = np.frombuffer((b" ".join(toks) + b" " * L)[:L],
                                dtype=np.uint8)
        docs[i] = row
    return docs, truth


def _textbook_minhash(docs, num_perms=64, shingle=5, seed=99):
    """The textbook formulation (one universal hash a permutation over the
    exact shingle set, one min each) in plain NumPy: no code, spec or hash
    family shared with ``ops.minhash``'s survivor sketch, so agreement
    between the two rankings is a finding, not an identity."""
    rng = np.random.RandomState(seed)
    p = np.uint64((1 << 61) - 1)
    # a < 2^23 keeps a*x + b under 2^64 for 40-bit shingles
    a = rng.randint(1, 1 << 23, size=num_perms).astype(np.uint64)
    b = rng.randint(0, 1 << 61, size=num_perms).astype(np.uint64)
    sigs = np.zeros((len(docs), num_perms), dtype=np.uint64)
    for i, doc in enumerate(docs):
        row = doc.astype(np.uint64)
        x = np.zeros(len(row) - shingle + 1, dtype=np.uint64)
        for k in range(shingle):
            x |= row[k:len(row) - shingle + 1 + k] << np.uint64(8 * k)
        y = a[:, None] * np.unique(x)[None, :] + b[:, None]
        y = (y >> np.uint64(61)) + (y & p)          # mod the Mersenne prime
        sigs[i] = np.where(y >= p, y - p, y).min(axis=1)
    return sigs


def test_lsh_recall_against_truth_and_a_textbook_minhash():
    from fastdfs_tpu.dedup.index import MinHashLSHIndex

    n_base = 8
    docs, truth = _crawl(n_base)
    lens = np.full(len(docs), docs.shape[1], dtype=np.int32)
    sigs = np.asarray(M.minhash_batch(docs, lens))
    queries = [int(q) for q in np.nonzero(truth >= 0)[0]]
    assert len(queries) >= 12 and (truth[n_base:] < 0).sum() >= 2  # bait is in
    idx = MinHashLSHIndex(64, 16)
    for d in range(len(docs)):
        if d not in queries:        # bases and distractors; variants only ask
            idx.add(sigs[d], d)
    top = {q: [ref for ref, _ in idx.query(sigs[q], top_k=5,
                                           min_similarity=0.0)]
           for q in queries}
    recall1 = sum(top[q][:1] == [truth[q]] for q in queries) / len(queries)
    recall5 = sum(truth[q] in top[q] for q in queries) / len(queries)
    assert recall5 >= recall1 >= 0.98
    witness = _textbook_minhash(docs)
    agree = sum(
        top[q][:1] == [int(np.argmax(
            (witness[:n_base] == witness[q]).mean(axis=1)))]
        for q in queries) / len(queries)
    assert agree >= 0.98
