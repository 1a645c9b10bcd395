"""Dataset pipeline tests: PTS format round-trip, loader determinism/resume,
conversion packing, unigram counts (SURVEY.md §4: we build the pyramid the
reference lacks)."""

import json
from collections import Counter

import numpy as np
import pytest

from photon_tpu.data import (
    LoaderState,
    ShardedDataset,
    ShardWriter,
    StreamingLoader,
    count_tokens,
    make_synthetic_dataset,
    merge_freq_dicts,
    probability_tensor,
)
from photon_tpu.data.convert import TokenPacker, convert_corpus
from photon_tpu.data.tokenizer import ByteTokenizer


def _write_range_dataset(path, n=100, seq=16, vocab=1000, per_shard=32):
    """Samples are [i, i, ...] so identity is visible from the value."""
    with ShardWriter(path, seq, vocab, per_shard) as w:
        for i in range(n):
            w.write(np.full(seq, i, np.int64))
    return ShardedDataset(path)


def test_shard_roundtrip(tmp_path):
    ds = _write_range_dataset(tmp_path / "ds", n=100, per_shard=32)
    assert len(ds) == 100
    assert len(ds.shard_sizes) == 4  # 32+32+32+4
    assert ds.shard_sizes[-1] == 4
    for i in [0, 31, 32, 99]:
        assert (ds[i] == i).all()
    assert ds.dtype == np.uint16


def test_shard_validation(tmp_path):
    ds = _write_range_dataset(tmp_path / "ds", n=10)
    ShardedDataset(tmp_path / "ds", validate=True)  # checksums ok
    with pytest.raises(IndexError):
        ds[10]
    with pytest.raises(ValueError):
        with ShardWriter(tmp_path / "bad", 8, vocab_size=4) as w:
            w.write(np.full(8, 99, np.int64))  # token >= vocab


def test_uint32_for_large_vocab(tmp_path):
    with ShardWriter(tmp_path / "big", 4, vocab_size=1 << 17) as w:
        w.write(np.full(4, 100_000, np.int64))
    ds = ShardedDataset(tmp_path / "big")
    assert ds.dtype == np.uint32
    assert (ds[0] == 100_000).all()


@pytest.mark.parametrize("vocab, dtype", [(60_000, np.uint16), (1 << 17, np.uint32)])
def test_batch_widens_rows_across_shards_to_int32(tmp_path, vocab, dtype):
    """``batch`` writes each row out of its mapped shard into one int32
    array, whichever of the two on-disk dtypes the vocabulary asked for."""
    top = vocab - 1  # past the signed range of a type as wide as the shard's
    with ShardWriter(tmp_path / "ds", 6, vocab, samples_per_shard=4) as w:
        for i in range(10):
            w.write(np.full(6, top - i, np.int64))
    ds = ShardedDataset(tmp_path / "ds")
    assert ds.dtype == dtype and len(ds.shard_sizes) == 3
    idxs = np.array([9, 0, 4, 3, 8])
    got = ds.batch(idxs)
    assert got.dtype == np.int32 and got.shape == (5, 6)
    assert (got == (top - idxs)[:, None]).all()
    assert ds.batch(np.array([], np.int64)).shape == (0, 6)
    with pytest.raises(IndexError):
        ds.batch(np.array([10]))


def test_loader_epoch_is_permutation(tmp_path):
    ds = _write_range_dataset(tmp_path / "ds", n=100)
    loader = StreamingLoader(ds, batch_size=10, seed=3, shuffle_block_size=16)
    seen = [int(b[j, 0]) for _ in range(10) for j, b in [(j, next(loader)) for j in range(10)]]
    # one epoch = each sample exactly once
    first_epoch = []
    loader2 = StreamingLoader(ds, batch_size=10, seed=3, shuffle_block_size=16)
    for _ in range(10):
        first_epoch.extend(int(v) for v in next(loader2)[:, 0])
    assert sorted(first_epoch) == list(range(100))
    assert first_epoch != list(range(100))  # actually shuffled
    del seen


def test_loader_determinism_and_resume(tmp_path):
    ds = _write_range_dataset(tmp_path / "ds", n=100)
    a = StreamingLoader(ds, batch_size=7, seed=5)
    ref = [next(a) for _ in range(30)]  # crosses epoch boundaries

    b = StreamingLoader(ds, batch_size=7, seed=5)
    for i in range(10):
        np.testing.assert_array_equal(next(b), ref[i])
    state = json.loads(json.dumps(b.state_dict()))  # serializable
    c = StreamingLoader(ds, batch_size=7, seed=5, state=LoaderState.from_dict(state))
    for i in range(10, 30):
        np.testing.assert_array_equal(next(c), ref[i])


def test_loader_epochs_differ(tmp_path):
    ds = _write_range_dataset(tmp_path / "ds", n=50)
    loader = StreamingLoader(ds, batch_size=50, seed=1, shuffle_block_size=8)
    e0, e1 = next(loader)[:, 0], next(loader)[:, 0]
    assert sorted(e0) == sorted(e1)
    assert list(e0) != list(e1)


def test_loader_skip_samples(tmp_path):
    ds = _write_range_dataset(tmp_path / "ds", n=40)
    a = StreamingLoader(ds, batch_size=4, seed=2)
    for _ in range(5):
        next(a)
    b = StreamingLoader(ds, batch_size=4, seed=2)
    b.skip_samples(20)
    np.testing.assert_array_equal(next(a), next(b))


def test_token_packer():
    p = TokenPacker(seq_len=5, eos_id=0)
    out = list(p.pack(np.array([1, 2, 3])))  # + eos -> 4 toks, no full row
    assert out == []
    out = list(p.pack(np.array([4, 5, 6])))  # tail 1,2,3,0 + 4,5,6,0 = 8 -> one row
    assert len(out) == 1
    np.testing.assert_array_equal(out[0], [1, 2, 3, 0, 4])
    # tail continues the stream exactly
    out2 = list(p.pack(np.array([7, 8])))
    np.testing.assert_array_equal(out2[0], [5, 6, 0, 7, 8])


def test_convert_corpus_partitions_and_freqs(tmp_path):
    tok = ByteTokenizer()
    docs = ["hello world", "abcdef" * 10, "xyz" * 30, "more text here"] * 6
    summary = convert_corpus(docs, tmp_path / "out", tok, n_clients=2, seq_len=8, split="train")
    assert summary["total_samples"] > 0
    sizes = []
    for i in range(2):
        ds = ShardedDataset(tmp_path / "out" / f"client_{i}" / "train")
        sizes.append(len(ds))
        freq_file = tmp_path / "out" / f"client_{i}" / "train" / "unigram_freq.json"
        assert freq_file.exists()
    assert abs(sizes[0] - sizes[1]) <= 1  # round-robin balance
    assert sum(sizes) == summary["total_samples"]


def test_unigram_probability_tensor(tmp_path):
    ds = make_synthetic_dataset(tmp_path / "syn", n_samples=8, seq_len=32, vocab_size=64)
    counts = count_tokens(ds)
    assert sum(counts.values()) == 8 * 32
    merged = merge_freq_dicts([counts, Counter({0: 5})])
    assert merged[0] == counts[0] + 5
    probs = probability_tensor(counts, 64)
    assert probs.shape == (64,)
    np.testing.assert_allclose(probs.sum(), 1.0, rtol=1e-6)


def test_synthetic_dataset_deterministic(tmp_path):
    a = make_synthetic_dataset(tmp_path / "a", n_samples=16, seq_len=16, vocab_size=100, seed=7)
    b = make_synthetic_dataset(tmp_path / "b", n_samples=16, seq_len=16, vocab_size=100, seed=7)
    for i in range(16):
        np.testing.assert_array_equal(a[i], b[i])
