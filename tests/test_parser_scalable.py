"""Scalable text ingestion: chunked C-tokenized reading and the two-round
low-memory mode (dataset_loader.cpp:741-840)."""

import numpy as np

import lightgbm_tpu.core.parser as parser_mod
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.parser import load_file_to_dataset


def _timed(fn, *args):
    import time
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def _write_csv(path, y, X, extra_cols=()):
    cols = [y] + list(extra_cols) + [X[:, j] for j in range(X.shape[1])]
    np.savetxt(path, np.column_stack(cols), delimiter=",", fmt="%.6f")
    return str(path)


def test_two_round_matches_default(rng, tmp_path, monkeypatch):
    # several chunks worth of rows; sample covers everything so the
    # two-round reservoir and the default path see identical samples
    monkeypatch.setattr(parser_mod, "_CHUNK_ROWS", 400)
    n = 1000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] > 0).astype(float)
    f = _write_csv(tmp_path / "d.csv", y, X)

    ds_a = load_file_to_dataset(f, Config(verbosity=-1))
    ds_b = load_file_to_dataset(f, Config(verbosity=-1, two_round=True))
    assert ds_b.num_data == n
    np.testing.assert_array_equal(ds_a.binned, ds_b.binned)
    np.testing.assert_allclose(ds_a.metadata.label, ds_b.metadata.label)
    for ma, mb in zip(ds_a.bin_mappers, ds_b.bin_mappers):
        np.testing.assert_allclose(ma.bin_upper_bound, mb.bin_upper_bound)


def test_two_round_weight_and_group_columns(rng, tmp_path):
    n = 600
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] > 0).astype(float)
    w = rng.uniform(0.5, 2.0, size=n).round(4)
    qid = np.repeat(np.arange(n // 50), 50).astype(float)
    f = _write_csv(tmp_path / "d.csv", y, X, extra_cols=(w, qid))
    cfg = Config(verbosity=-1, two_round=True, weight_column="1",
                 group_column="2")
    ds = load_file_to_dataset(f, cfg)
    assert ds.num_total_features == 4
    np.testing.assert_allclose(ds.metadata.weights, w, rtol=1e-5)
    assert ds.metadata.query_boundaries is not None
    assert len(ds.metadata.query_boundaries) == n // 50 + 1


def test_two_round_valid_set_reuses_reference_bins(rng, tmp_path):
    n = 500
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] > 0).astype(float)
    ftr = _write_csv(tmp_path / "train.csv", y, X)
    fva = _write_csv(tmp_path / "valid.csv", y[:200], X[:200])
    cfg = Config(verbosity=-1, two_round=True)
    train = load_file_to_dataset(ftr, cfg)
    valid = load_file_to_dataset(fva, cfg, reference=train)
    assert valid.bin_mappers is train.bin_mappers
    assert valid.binned.shape == (200, train.num_columns)
    # quantization through the reference mappers matches direct binning
    direct = train.create_valid(X[:200], y[:200])
    np.testing.assert_array_equal(valid.binned, direct.binned)


def test_reservoir_sample_bounded(rng, tmp_path, monkeypatch):
    """When rows exceed bin_construct_sample_cnt, the reservoir holds
    exactly that many rows and binning still succeeds."""
    monkeypatch.setattr(parser_mod, "_CHUNK_ROWS", 300)
    n = 2000
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 0).astype(float)
    f = _write_csv(tmp_path / "d.csv", y, X)
    cfg = Config(verbosity=-1, two_round=True, bin_construct_sample_cnt=500)
    ds = load_file_to_dataset(f, cfg)
    assert ds.num_data == n
    assert ds.binned.shape[0] == n
    # bins were fit from a 500-row sample but cover the full data range
    assert all(m.num_bin >= 2 for m in ds.bin_mappers)


def test_file_io_scheme_seam(tmp_path):
    """VirtualFileReader/Writer-equivalent seam (file_io.h:20): local
    paths pass through; registered schemes route to their handler;
    unregistered schemes raise a clear error."""
    import io

    import pytest

    from lightgbm_tpu.utils import file_io
    from lightgbm_tpu.utils.log import LightGBMError

    p = tmp_path / "x.csv"
    p.write_text("1,2\n")
    with file_io.open_file(str(p)) as fh:
        assert fh.read() == "1,2\n"
    assert file_io.exists(str(p))
    assert not file_io.exists(str(tmp_path / "missing.csv"))

    store = {"mem://a.csv": b"0,1\n2,3\n"}

    def opener(path, mode="r"):
        data = store[path]
        return io.StringIO(data.decode()) if "b" not in mode \
            else io.BytesIO(data)

    file_io.register_scheme("mem", opener)
    try:
        with file_io.open_file("mem://a.csv") as fh:
            assert fh.read().startswith("0,1")
        assert file_io.exists("mem://a.csv")
        # and the dataset loader reads through the seam end-to-end
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.core.parser import load_file_to_dataset
        store["mem://train.csv"] = (
            "\n".join(f"{i % 2},{i},{i * 2}" for i in range(64)) + "\n"
        ).encode()
        ds = load_file_to_dataset("mem://train.csv",
                                  Config(verbosity=-1, min_data_in_leaf=2))
        assert ds.num_data == 64
    finally:
        file_io.unregister_scheme("mem")

    with pytest.raises(LightGBMError, match="No file-IO handler"):
        file_io.open_file("hdfs://nn/path.csv")


def test_fsspec_backend_round_trip(tmp_path):
    """A REAL filesystem backend behind the seam (reference ships HDFS,
    src/io/file_io.cpp:60,99): fsspec's in-memory filesystem plays the
    remote store, with zero egress.  Covers model save/load and binary
    dataset save/load through `memory://` URIs end-to-end, plus the
    unregistered-scheme auto-registration path."""
    import numpy as np
    import pytest

    fsspec = pytest.importorskip("fsspec")

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import file_io

    rng = np.random.RandomState(11)
    X = rng.normal(size=(600, 5))
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 7}, lgb.Dataset(X, y),
                    num_boost_round=3, verbose_eval=False)
    pred = bst.predict(X)

    try:
        # NOT pre-registered: open_file must auto-register via fsspec
        file_io.unregister_scheme("memory")
        bst.save_model("memory://bucket/model.txt")
        bst2 = lgb.Booster(model_file="memory://bucket/model.txt")
        np.testing.assert_array_equal(pred, bst2.predict(X))
        assert file_io.exists("memory://bucket/model.txt")
        assert not file_io.exists("memory://bucket/nope.txt")

        # binary dataset cache through the same transport
        ds = lgb.Dataset(X, y)
        ds.construct()
        ds._handle.save_binary("memory://bucket/train.bin")
        from lightgbm_tpu.core.dataset import TpuDataset
        ds2 = TpuDataset.load_binary("memory://bucket/train.bin")
        assert ds2.num_data == 600
    finally:
        file_io.unregister_scheme("memory")


def test_native_libsvm_tokenizer_parity(tmp_path):
    """src/native/textparse.cpp must reproduce the Python LibSVM parser
    (the spec) exactly — including 0/1-based indices, out-of-order
    tokens, blank lines, nan values, and skipped qid: prefixes — and be
    faster on a ~100k-token file."""
    import time

    import numpy as np
    import pytest

    from lightgbm_tpu.core import parser
    from lightgbm_tpu.core.native import parse_libsvm_native, text_lib

    if text_lib() is None:
        pytest.skip("no C++ toolchain")

    rng = np.random.RandomState(5)
    lines = []
    for i in range(4000):
        feats = sorted(rng.choice(40, size=rng.randint(1, 12),
                                  replace=False))
        toks = [f"{rng.normal():.6g}"]
        if i % 7 == 0:
            toks.append(f"qid:{i // 50}")      # skipped by both parsers
        toks += [f"{f}:{rng.normal():.6g}" for f in feats]
        if i % 211 == 0:
            toks.append("5:nan")
        lines.append(" ".join(toks))
        if i % 97 == 0:
            lines.append("")                   # blank lines are dropped
    text = "\n".join(lines) + "\n"

    expected = parser._parse_libsvm(text.splitlines())
    got = parse_libsvm_native(text.encode())
    assert got is not None
    np.testing.assert_array_equal(
        np.isnan(expected), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got),
                               np.nan_to_num(expected), rtol=0, atol=0)

    # end-to-end through load_file_to_dataset (native path inside)
    p = tmp_path / "train.libsvm"
    p.write_text(text)
    from lightgbm_tpu.config import Config
    ds = parser.load_file_to_dataset(str(p),
                                     Config(verbosity=-1,
                                            min_data_in_leaf=2))
    assert ds.num_data == expected.shape[0]

    # throughput: the native pass must beat the interpreter loop.  The
    # bound is 2x where ~5-8x is measured on an idle host: at 5x this
    # assertion failed under six loaded test workers.  INTERLEAVED
    # best-of-3 exposes both sides to the same sustained load instead of
    # letting one side eat a bursty phase alone.
    big = (text * 10).encode()
    big_lines = big.decode().splitlines()
    t_native, t_python = [], []
    for _ in range(3):
        t_native.append(_timed(parse_libsvm_native, big))
        t_python.append(_timed(parser._parse_libsvm, big_lines))
    assert min(t_native) * 2 < min(t_python), (t_native, t_python)


def test_native_libsvm_rejects_malformed():
    """Malformed labels/values must NOT silently parse natively — the
    Python parser is the spec and it raises; the native pass returns
    None so the caller reaches that behavior."""
    import pytest

    from lightgbm_tpu.core.native import parse_libsvm_native, text_lib

    if text_lib() is None:
        pytest.skip("no C++ toolchain")
    for bad in (b"N/A 1:2.0\n", b"1.0 3:abc\n", b"1.0 3:0x10\n",
                b"1.0 3:\n", b"1.0 -1:5\n"):
        assert parse_libsvm_native(bad) is None, bad
    # and well-formed edge tokens still parse
    ok = parse_libsvm_native(b"1.0 0:nan 2:1e5\r\n\n-2 1:+.5\n")
    assert ok is not None and ok.shape == (2, 4)

