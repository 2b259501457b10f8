"""Native (C) Avro block decoder vs the pure-Python codec.

VERDICT r2 item 9: corpus-scale ingest must not bottleneck in the
per-record Python decode.  The contract tested here: identical results to
the pure-Python codec on every supported schema shape, graceful fallback on
unsupported shapes, and a decode rate far above the Python path.
"""
import time

import numpy as np
import pytest

from photon_ml_tpu.data.avro_codec import read_container, write_container
from photon_ml_tpu.data.avro_io import (
    TRAINING_EXAMPLE_AVRO, read_training_examples, write_training_examples,
)
from photon_ml_tpu.data.avro_native import compile_schema, read_columnar
from photon_ml_tpu.data.index_map import build_index_map


def test_native_build_failure_is_recorded_not_silent(tmp_path, monkeypatch,
                                                     caplog):
    """A decoder that cannot be built falls back to the Python codec — and
    says so: `native_status()` carries the reason, a warning is logged, and
    the per-file counter names the decoder that ran.  The build itself goes
    to a clean path (the .so is a build product, never a committed file)."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.data import avro_native

    p = str(tmp_path / "tricky.avro")
    _write_tricky(p)
    monkeypatch.setattr(avro_native, "_lib", None)
    monkeypatch.setattr(avro_native, "_lib_error", None)
    monkeypatch.setattr(avro_native, "_SO", str(tmp_path / "libavrodec.so"))

    # a clean target builds from the committed C source and decodes
    native0 = telemetry.counter("avro.decode.native").value
    assert avro_native.native_status() == {"decoder": "native",
                                           "reason": None}
    assert read_columnar(p) is not None
    assert telemetry.counter("avro.decode.native").value == native0 + 1

    # a source that does not compile: stated fallback, with the reason
    broken = tmp_path / "broken.c"
    broken.write_text("this is not C\n")
    monkeypatch.setattr(avro_native, "_lib", None)
    monkeypatch.setattr(avro_native, "_lib_error", None)
    monkeypatch.setattr(avro_native, "_SRC", str(broken))
    monkeypatch.setattr(avro_native, "_SO", str(tmp_path / "libbroken.so"))
    python0 = telemetry.counter("avro.decode.python").value
    with caplog.at_level("WARNING", logger=avro_native.logger.name):
        assert read_columnar(p) is None
    status = avro_native.native_status()
    assert status["decoder"] == "python" and "cc failed" in status["reason"]
    assert "native Avro decoder unavailable" in caplog.text
    assert telemetry.counter("avro.decode.python").value == python0 + 1
    assert not (tmp_path / "libbroken.so").exists()


def _write_tricky(path, n=60, seed=3):
    """Records exercising null unions, empty feature lists, and both codecs'
    varint edge cases (negative longs via zigzag doubles etc.)."""
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        feats = [{"name": f"f{j}", "term": "t" if j % 2 else "",
                  "value": float(rng.normal())}
                 for j in range(int(rng.integers(0, 5)))]
        recs.append({
            "uid": None if i % 3 == 0 else f"uid-{i}",
            "label": float(rng.normal()) * (10 ** int(rng.integers(-3, 4))),
            "features": feats,
            "metadataMap": None if i % 2 else {"k": "v", "x": "y"},
            "weight": None if i % 4 else float(rng.uniform(0.1, 5)),
            "offset": None if i % 5 else float(rng.normal()),
        })
    write_container(path, TRAINING_EXAMPLE_AVRO, recs)
    return recs


def test_native_matches_python_codec(tmp_path):
    p = str(tmp_path / "tricky.avro")
    recs = _write_tricky(p)
    cols = read_columnar(p)
    assert cols is not None, "native decoder unavailable"
    py = list(read_container(p))
    assert py == recs

    np.testing.assert_allclose(cols["label"], [r["label"] for r in recs],
                               rtol=0, atol=0)
    np.testing.assert_array_equal(
        cols["uid#present"], [0 if r["uid"] is None else 1 for r in recs])
    assert [u for u in cols["uid"].to_list() if u] == \
        [r["uid"] for r in recs if r["uid"] is not None]
    np.testing.assert_array_equal(cols["features#count"],
                                  [len(r["features"]) for r in recs])
    flat = [f for r in recs for f in r["features"]]
    assert cols["features.name"].to_list() == [f["name"] for f in flat]
    assert cols["features.term"].to_list() == [f["term"] for f in flat]
    np.testing.assert_allclose(cols["features.value"],
                               [f["value"] for f in flat], rtol=0, atol=0)
    w = [r["weight"] for r in recs]
    np.testing.assert_array_equal(cols["weight#present"],
                                  [0 if v is None else 1 for v in w])
    got_w = cols["weight"][cols["weight#present"] == 1]
    np.testing.assert_allclose(got_w, [v for v in w if v is not None])


def test_reader_native_equals_fallback(tmp_path, rng, monkeypatch):
    """read_training_examples must give identical output with the native
    path disabled (the pure-Python fallback is the reference semantics)."""
    imap = build_index_map([("a", ""), ("b", ""), ("c", "t")])
    n = 50
    x = np.zeros((n, imap.size))
    x[:, :3] = rng.normal(size=(n, 3)) * (rng.uniform(size=(n, 3)) > 0.5)
    x[:, imap.intercept_index] = 1.0
    y = rng.normal(size=n)
    p = str(tmp_path / "t.avro")
    write_training_examples(p, x, y, imap,
                            uids=[f"u{i}" for i in range(n)])

    fast = read_training_examples(p, imap)
    import photon_ml_tpu.data.avro_io as aio
    monkeypatch.setattr(aio, "_read_training_examples_native",
                        lambda *a: None)
    slow = read_training_examples(p, imap)
    np.testing.assert_allclose(fast[0], slow[0])
    np.testing.assert_allclose(fast[1], slow[1])
    assert fast[2] is None and slow[2] is None
    assert fast[4] == slow[4]


def test_unsupported_schema_falls_back():
    # union with two non-null branches: not compilable -> None
    schema = {"type": "record", "name": "R", "fields": [
        {"name": "v", "type": ["string", "long"]}]}
    assert compile_schema(schema) is None
    # multi-branch union
    schema2 = {"type": "record", "name": "R2", "fields": [
        {"name": "v", "type": ["null", "string", "long"]}]}
    assert compile_schema(schema2) is None


def test_decode_throughput(tmp_path):
    """The C decoder must beat the Python codec by a wide margin; the
    absolute rate is printed for the bench record."""
    rng = np.random.default_rng(9)
    n = 20_000
    recs = [{"uid": f"uid-{i}", "label": float(rng.normal()),
             "features": [{"name": f"f{j}", "term": "", "value": 1.0}
                          for j in range(10)],
             "metadataMap": None, "weight": None, "offset": None}
            for i in range(n)]
    p = str(tmp_path / "big.avro")
    # codec null: the rate should measure decode, not zlib on synthetic
    # highly-compressible data
    write_container(p, TRAINING_EXAMPLE_AVRO, recs, codec="null")
    nbytes = __import__("os").path.getsize(p)

    cols = read_columnar(p)  # warm-up: compiles/loads the C library
    assert cols is not None and len(cols["label"]) == n
    t0 = time.perf_counter()
    cols = read_columnar(p)
    native_s = time.perf_counter() - t0
    assert len(cols["label"]) == n

    t0 = time.perf_counter()
    n_py = sum(1 for _ in read_container(p))
    python_s = time.perf_counter() - t0
    assert n_py == n

    rate = nbytes / native_s / 1e6
    print(f"native: {rate:.0f} MB/s, python: {nbytes / python_s / 1e6:.1f} "
          f"MB/s, speedup {python_s / native_s:.0f}x")
    assert native_s * 3 < python_s, (native_s, python_s)


def test_recursive_schema_rejected():
    """A self-referential record has no flat op program; compile_schema must
    return None (fallback) instead of recursing unboundedly (ADVICE r3)."""
    from photon_ml_tpu.data.avro_native import compile_schema
    schema = {"type": "record", "name": "Node", "fields": [
        {"name": "value", "type": "long"},
        {"name": "next", "type": ["null", "Node"]},
    ]}
    assert compile_schema(schema) is None


def test_named_record_reuse_compiles():
    """Non-recursive reuse of a named record type must compile (each use
    site gets its own columns), not crash."""
    from photon_ml_tpu.data.avro_native import compile_schema
    schema = {"type": "record", "name": "Outer", "fields": [
        {"name": "a", "type": {"type": "record", "name": "Inner", "fields": [
            {"name": "v", "type": "long"}]}},
        {"name": "b", "type": "Inner"},
    ]}
    plan = compile_schema(schema)
    assert plan is not None
    assert [c for c, _ in plan.columns] == ["a.v", "b.v"]


def test_native_decoder_survives_corrupt_blocks(tmp_path, rng):
    """Fuzz: random byte corruptions of a valid container must produce a
    clean Python error (or a successful parse of still-valid bytes) —
    never a crash of the C decoder (bounds checks, varint limits,
    recursion guard)."""
    import photon_ml_tpu.data.avro_native as an
    from photon_ml_tpu.data.avro_io import write_training_examples
    from photon_ml_tpu.data.index_map import build_index_map

    imap = build_index_map([(f"f{i}", "") for i in range(6)])
    n = 50
    x = np.zeros((n, imap.size), np.float32)
    x[:, :-1] = (rng.uniform(size=(n, 6)) < 0.5).astype(np.float32)
    x[:, -1] = 1.0
    y = rng.uniform(size=n)
    base = tmp_path / "clean.avro"
    write_training_examples(str(base), x, y, imap,
                            uids=[f"r{i}" for i in range(n)])
    raw = bytearray(base.read_bytes())

    survived = 0
    for trial in range(150):
        buf = bytearray(raw)
        for _ in range(rng.integers(1, 6)):
            pos = int(rng.integers(16, len(buf)))  # keep the magic intact
            buf[pos] = int(rng.integers(0, 256))
        p = tmp_path / "fuzz.avro"
        p.write_bytes(bytes(buf))
        try:
            an.read_columnar(str(p))
        except Exception:
            pass  # clean Python error is fine; a segfault would kill pytest
        survived += 1
    assert survived == 150
