"""Streaming libFFM reader + system utils + CLI text subcommands."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lightctr_tpu.data import load_libffm
from lightctr_tpu.data.streaming import iter_libffm_batches
from lightctr_tpu.utils import host_memory_usage

REF_SPARSE = "/root/reference/data/train_sparse.csv"


def test_streaming_matches_eager():
    ds = load_libffm(REF_SPARSE)
    batches = list(
        iter_libffm_batches(REF_SPARSE, batch_size=128, max_nnz=ds.max_nnz)
    )
    assert len(batches) == 1000 // 128
    first = batches[0]
    np.testing.assert_array_equal(first["fids"], ds.fids[:128])
    np.testing.assert_array_equal(first["fields"], ds.fields[:128])
    np.testing.assert_allclose(first["vals"], ds.vals[:128])
    np.testing.assert_allclose(first["labels"], ds.labels[:128])
    assert first["row_mask"].sum() == 128


def test_streaming_truncation_and_tail():
    batches = list(
        iter_libffm_batches(
            REF_SPARSE, batch_size=300, max_nnz=10, drop_remainder=False
        )
    )
    assert len(batches) == 4  # 3 full + padded tail of 100
    assert batches[0]["fids"].shape == (300, 10)
    tail = batches[-1]
    assert tail["row_mask"].sum() == 100
    assert np.all(tail["mask"][100:] == 0)


def test_streaming_vocab_folding():
    b = next(iter_libffm_batches(REF_SPARSE, batch_size=16, max_nnz=50, feature_cnt=1000))
    assert b["fids"].max() < 1000


def test_host_memory_usage():
    m = host_memory_usage()
    assert m.get("MemTotal", 0) > 0


def test_cli_plsa_and_embed(tmp_path):
    text_path = str(tmp_path / "corpus.txt")
    with open(text_path, "w") as f:
        for i in range(30):
            f.write(("apple banana cherry date " if i % 2 else "wolf bear fox lynx ") * 5 + "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "lightctr_tpu.cli", "plsa", "--data", text_path,
         "--topics", "2", "--epochs", "40", "--top-words", "3"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(rep["topics"]) == 2 and len(rep["topics"][0]) == 3

    emb_path = str(tmp_path / "emb.txt")
    out = subprocess.run(
        [sys.executable, "-m", "lightctr_tpu.cli", "embed", "--data", text_path,
         "--dim", "8", "--epochs", "2", "--window", "2", "--batch-size", "64",
         "--out", emb_path],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert os.path.exists(emb_path) and rep["n_pairs"] > 0


def test_native_stream_matches_python(tmp_path, rng):
    """The C chunk parser and the Python generator yield identical batch
    streams (incl. truncation of over-long rows, id folding, tail padding)."""
    from lightctr_tpu.native.bindings import available

    if not available():
        pytest.skip("native library unavailable")
    path = tmp_path / "s.ffm"
    with open(path, "w") as f:
        for i in range(37):
            nnz = rng.integers(1, 9)  # some rows exceed max_nnz=5 -> truncate
            toks = " ".join(
                f"{rng.integers(0, 7)}:{rng.integers(0, 999)}:{rng.random():.3f}"
                for _ in range(nnz)
            )
            f.write(f"{i % 2} {toks}\n")
            if i % 11 == 0:
                f.write("\n")  # blank lines are skipped
    kw = dict(batch_size=8, max_nnz=5, feature_cnt=100, field_cnt=4)
    for drop in (True, False):
        a = list(iter_libffm_batches(str(path), drop_remainder=drop, native=True, **kw))
        b = list(iter_libffm_batches(str(path), drop_remainder=drop, native=False, **kw))
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert set(x) == set(y)
            for k in y:
                np.testing.assert_array_equal(x[k], y[k])


def test_native_large_ids_fold_and_error(tmp_path):
    """Ids beyond int32: with a fold both paths agree (exact long fold,
    libffm_parser.cpp ffm_parse_chunk); without one the native path raises
    instead of silently ending the stream (rc=-3)."""
    from lightctr_tpu.native.bindings import available

    if not available():
        pytest.skip("native library unavailable")
    path = tmp_path / "big.ffm"
    with open(path, "w") as f:
        f.write("1 3:5000000000:1.0 1:2:0.5\n")
        f.write("0 2:7:1.0 0:4999999999:2.0\n")
        f.write("1 2:-5:1.0 1:3:0.5\n")  # negative id: Python-% fold parity
    kw = dict(batch_size=3, max_nnz=4, feature_cnt=1000, field_cnt=4)
    a = list(iter_libffm_batches(str(path), native=True, **kw))
    b = list(iter_libffm_batches(str(path), native=False, **kw))
    assert len(a) == len(b) == 1
    for k in b[0]:
        np.testing.assert_array_equal(a[0][k], b[0][k])
    assert a[0]["fids"].max() < 1000
    with pytest.raises(ValueError, match="int32"):
        list(iter_libffm_batches(str(path), native=True, batch_size=3, max_nnz=4))


def _real_rows(batches):
    """Stack the real rows of a batch stream into flat arrays."""
    out = {}
    for b in batches:
        n = int(b["row_mask"].sum())
        for k, v in b.items():
            if k == "row_mask":
                continue
            out.setdefault(k, []).append(v[:n])
    return {k: np.concatenate(v) for k, v in out.items()}


def test_strided_shards_partition_the_stream():
    """proc_file_split parity: the per-process shards are disjoint, strided,
    and their union is the whole file."""
    full = _real_rows(
        iter_libffm_batches(
            REF_SPARSE, batch_size=128, max_nnz=30, drop_remainder=False
        )
    )
    pc = 3
    shards = [
        _real_rows(
            iter_libffm_batches(
                REF_SPARSE, batch_size=128, max_nnz=30, drop_remainder=False,
                process_index=w, process_count=pc,
            )
        )
        for w in range(pc)
    ]
    for w, sh in enumerate(shards):
        np.testing.assert_array_equal(sh["fids"], full["fids"][w::pc])
        np.testing.assert_allclose(sh["labels"], full["labels"][w::pc])
    assert sum(len(s["labels"]) for s in shards) == len(full["labels"])


def test_strided_native_matches_python():
    for w in range(2):
        kw = dict(
            batch_size=64, max_nnz=30, process_index=w, process_count=2
        )
        nat = list(iter_libffm_batches(REF_SPARSE, native=True, **kw))
        py = list(iter_libffm_batches(REF_SPARSE, native=False, **kw))
        assert len(nat) == len(py)
        for a, b in zip(nat, py):
            for k in a:
                np.testing.assert_allclose(a[k], b[k], err_msg=k)


def test_strided_validates_args():
    with pytest.raises(ValueError):
        next(iter_libffm_batches(REF_SPARSE, 8, 4, process_index=1))
    with pytest.raises(ValueError):
        next(
            iter_libffm_batches(
                REF_SPARSE, 8, 4, process_index=2, process_count=2
            )
        )


def test_strided_workers_yield_equal_batch_counts(tmp_path):
    """SPMD lockstep: every worker must yield the SAME number of full
    batches regardless of the file's tail (255 rows, B=128, 2 workers:
    worker 0 owns 128 rows but must NOT yield a batch worker 1 can't
    match)."""
    p = tmp_path / "uneven.ffm"
    with open(p, "w") as f:
        for i in range(255):
            f.write(f"{i % 2} 0:{i % 50}:1.0 1:{(i * 7) % 50}:1.0\n")
    for native in (False, True):
        counts = [
            len(list(iter_libffm_batches(
                str(p), batch_size=128, max_nnz=4, native=native,
                process_index=w, process_count=2,
            )))
            for w in range(2)
        ]
        assert counts[0] == counts[1] == 0, (native, counts)
    # 256 rows -> both workers own exactly 128 -> both yield 1
    with open(p, "a") as f:
        f.write("1 0:3:1.0\n")
    counts = [
        len(list(iter_libffm_batches(
            str(p), batch_size=128, max_nnz=4,
            process_index=w, process_count=2,
        )))
        for w in range(2)
    ]
    assert counts == [1, 1], counts


def _write_rows(path, n, start=0):
    with open(path, "w" if start == 0 else "a") as f:
        for i in range(start, start + n):
            f.write(f"{i % 2} 0:{i % 97}:1.0 1:{(i * 7) % 97}:2.0\n")


def test_loop_mode_wraps_exactly_at_the_epoch_boundary(tmp_path):
    """ISSUE 11 satellite: ``loop=True`` re-streams the file forever —
    2 epochs of the loop equal 2 back-to-back finite streams, including
    across the wrap boundary (no dropped/duplicated batch where epoch N
    ends and N+1 begins), and ``drop_remainder`` applies per epoch."""
    p = tmp_path / "loop.ffm"
    _write_rows(p, 21)  # B=4 -> 5 full batches + dropped tail, per epoch
    finite = list(iter_libffm_batches(str(p), 4, 4))
    assert len(finite) == 5
    it = iter_libffm_batches(str(p), 4, 4, loop=True)
    looped = [next(it) for _ in range(2 * len(finite))]
    for got, want in zip(looped, finite + finite):
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_loop_mode_reshuffles_deterministically_per_epoch(tmp_path):
    """The per-epoch shuffle is seeded ``(seed, epoch)``: the sequence is
    reproducible run-to-run, epochs 0 and 1 order their batches
    differently, and each epoch is a permutation of the finite stream
    (no batch lost or duplicated by the shuffle buffer)."""
    p = tmp_path / "shuf.ffm"
    _write_rows(p, 24)  # 6 batches of 4
    n = 6

    def epoch_keys(count):
        it = iter_libffm_batches(str(p), 4, 4, loop=True,
                                 shuffle_batches=4, seed=3)
        return [int(next(it)["fids"][0, 0]) for _ in range(count)]

    a, b = epoch_keys(2 * n), epoch_keys(2 * n)
    assert a == b, "shuffled loop must be deterministic for one seed"
    base = [int(x["fids"][0, 0]) for x in iter_libffm_batches(str(p), 4, 4)]
    assert sorted(a[:n]) == sorted(base) == sorted(a[n:])
    assert a[:n] != a[n:], "epochs must reshuffle, not repeat"
    c = iter_libffm_batches(str(p), 4, 4, loop=True, shuffle_batches=4,
                            seed=4)
    assert [int(next(c)["fids"][0, 0]) for _ in range(n)] != a[:n]


def test_loop_mode_stop_predicate_ends_the_stream(tmp_path):
    p = tmp_path / "stop.ffm"
    _write_rows(p, 8)
    seen = []
    stream = iter_libffm_batches(str(p), 2, 4, loop=True,
                                 stop=lambda: len(seen) >= 7)
    for b in stream:
        seen.append(b)
    assert len(seen) == 7  # mid-second-epoch: the predicate ended it


def test_follow_mode_tails_and_withholds_partial_lines(tmp_path):
    """ISSUE 11 satellite: ``follow=True`` tails a growing file.  A
    trailing PARTIAL line (writer mid-append, no newline yet) is never
    parsed — it would misread half a row or raise on a torn token — and
    is stitched whole once its newline lands."""
    import threading

    p = tmp_path / "tail.ffm"
    with open(p, "w") as f:
        f.write("0 0:1:1.0 1:2:1.0\n1 0:3:1.0\n")
        f.write("1 0:")  # torn mid-token: parsing it would raise
    ev = threading.Event()
    it = iter_libffm_batches(str(p), 2, 4, follow=True, stop=ev,
                             poll_s=0.01)
    b1 = next(it)  # the two COMPLETE lines; the torn tail waits
    assert int(b1["fids"][0, 0]) == 1 and int(b1["fids"][1, 0]) == 3
    assert b1["row_mask"].sum() == 2
    with open(p, "a") as f:
        f.write("5:2.5\n0 0:7:1.0\n")  # completes the torn line + one row
    b2 = next(it)
    assert int(b2["fids"][0, 0]) == 5  # the stitched line parsed as ONE row
    np.testing.assert_allclose(b2["vals"][0, 0], 2.5)
    assert int(b2["fids"][1, 0]) == 7
    ev.set()
    with pytest.raises(StopIteration):
        next(it)


def test_follow_and_loop_validate_args(tmp_path):
    p = tmp_path / "v.ffm"
    _write_rows(p, 4)
    with pytest.raises(ValueError, match="exclusive"):
        next(iter_libffm_batches(str(p), 2, 4, follow=True, loop=True))
    with pytest.raises(ValueError, match="shard"):
        next(iter_libffm_batches(str(p), 2, 4, follow=True,
                                 process_index=0, process_count=2))


def test_scan_level_shard_validates_rows_at_their_owner(tmp_path):
    """The native strided scan line-skips other workers' rows WITHOUT
    tokenizing them (the whole point: the fleet parses each row once).
    Contract: a malformed row raises in its OWNING worker's stream — so
    across a full fleet every row is still validated by exactly one
    worker — while non-owners stream past it."""
    p = tmp_path / "bad_row.ffm"
    with open(p, "w") as f:
        for i in range(64):
            if i == 33:  # worker 1's row (33 % 2 == 1)
                f.write("1 0:borked\n")
            else:
                f.write(f"{i % 2} 0:{i % 50}:1 1:{(i * 7) % 50}:2.5\n")
    # worker 1 owns the malformed row: must fail loud
    with pytest.raises(ValueError, match="bad libFFM token"):
        list(iter_libffm_batches(str(p), batch_size=16, max_nnz=4,
                                 native=True, drop_remainder=False,
                                 process_index=1, process_count=2))
    # worker 0 never tokenizes it: full shard, correct rows
    rows = _real_rows(iter_libffm_batches(
        str(p), batch_size=16, max_nnz=4, native=True,
        drop_remainder=False, process_index=0, process_count=2))
    assert len(rows["labels"]) == 32
    np.testing.assert_array_equal(rows["fids"][:, 0],
                                  np.arange(0, 64, 2) % 50)

def test_native_follow_preserves_the_partial_line_contract(tmp_path):
    """ISSUE 20 satellite: ``follow=True`` through the NATIVE chunk
    parser honors the same partial-trailing-line contract as the Python
    tailer — the parse bound stops at the last newline
    (``_newline_bound``), so a writer caught mid-append is never
    misread, and the torn line parses as ONE row once its newline
    lands."""
    import threading

    from lightctr_tpu.native.bindings import available

    if not available():
        pytest.skip("native library unavailable")
    p = tmp_path / "tail.ffm"
    with open(p, "w") as f:
        f.write("0 0:1:1.0 1:2:1.0\n1 0:3:1.0\n")
        f.write("1 0:")  # torn mid-token: parsing it would raise
    ev = threading.Event()
    it = iter_libffm_batches(str(p), 2, 4, follow=True, native=True,
                             stop=ev, poll_s=0.01)
    b1 = next(it)  # the two COMPLETE lines; the torn tail waits
    assert int(b1["fids"][0, 0]) == 1 and int(b1["fids"][1, 0]) == 3
    assert b1["row_mask"].sum() == 2
    with open(p, "a") as f:
        f.write("5:2.5\n0 0:7:1.0\n")  # completes the torn line + one row
    b2 = next(it)
    assert int(b2["fids"][0, 0]) == 5  # the stitched line parsed as ONE row
    np.testing.assert_allclose(b2["vals"][0, 0], 2.5)
    assert int(b2["fids"][1, 0]) == 7
    ev.set()
    with pytest.raises(StopIteration):
        next(it)


def test_native_follow_matches_python_follow(tmp_path):
    """Both tailers, fed the same growth increments, yield identical
    batches — the native path is a faster implementation of the same
    stream, not a different one."""
    import threading

    from lightctr_tpu.native.bindings import available

    if not available():
        pytest.skip("native library unavailable")
    p = tmp_path / "grow.ffm"
    _write_rows(p, 5)
    ev = threading.Event()
    its = [iter_libffm_batches(str(p), 4, 4, follow=True, native=nat,
                               stop=ev, poll_s=0.01)
           for nat in (True, False)]
    batches = [[next(it)] for it in its]
    _write_rows(p, 7, start=5)  # tail past another batch boundary
    for i, it in enumerate(its):
        batches[i].append(next(it))
    ev.set()
    for a, b in zip(*batches):
        for k in b:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_newline_bound_scans_back_to_the_last_newline(tmp_path):
    from lightctr_tpu.data.streaming import _newline_bound

    p = tmp_path / "b.txt"
    p.write_bytes(b"aaa\nbb\nccc")  # 10 bytes, last newline at 6
    assert _newline_bound(str(p), 0) == 7
    assert _newline_bound(str(p), 7) == 7  # only the torn tail remains
    p.write_bytes(b"no newline at all")
    assert _newline_bound(str(p), 0) == 0
