"""The part file of the Parquet sinks (``io/sink.py::_encode_part``): the
same table whatever its columns hold — every value, name and type as
pyarrow's defaults would have written them — with a dictionary only where a
column's values repeat, and the two registry counters that say which."""

import io
import os
import time
from types import SimpleNamespace

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from real_time_fraud_detection_system_tpu.io import sink as sink_mod
from real_time_fraud_detection_system_tpu.io.sink import (
    ParquetSink,
    StoreParquetSink,
    _result_to_columns,
)
from real_time_fraud_detection_system_tpu.io.store import S3Store
from real_time_fraud_detection_system_tpu.runtime.engine import BatchResult
from real_time_fraud_detection_system_tpu.utils.metrics import get_registry

from test_store import FakeS3Client  # noqa: E402 (pytest adds tests/ to path)

LIMIT = sink_mod._DICTIONARY_PAGE_LIMIT
WRITE_BATCH = 1024  # values the writer takes between two looks at the limit
WIDTH = {"INT32": 4, "INT64": 8, "DOUBLE": 8}
N_COLUMNS = 21


def _result(rows: int, holds: str) -> BatchResult:
    rng = np.random.default_rng(rows + len(holds))
    ids = np.arange(rows, dtype=np.int64)
    if holds == "distinct":  # every value of every column another
        key = ids * 7 + (1 << 33)
        feats = np.tile(ids.astype(np.float32)[:, None], (1, 15)) + 0.5
        feats[:, [1, 2, 3, 5, 7, 9, 11, 13]] -= 0.5  # the int32 columns
        probs = (ids + 1) / (rows + 1.0)
        amount = ids * 3 + 101
    elif holds == "constant":
        key = np.full(rows, 42, np.int64)
        feats = np.full((rows, 15), 3.0, np.float32)
        probs = np.full(rows, 0.25)
        amount = np.full(rows, 999, np.int64)
    elif holds == "few":  # seven values a column
        key = rng.integers(0, 7, rows) * 1_000_003
        feats = rng.integers(0, 7, (rows, 15)).astype(np.float32)
        probs = rng.integers(0, 7, rows) / 8.0
        amount = rng.integers(0, 7, rows) * 250 + 100
    elif holds == "served":  # what a forest's batch holds
        key = rng.integers(0, 1 << 22, rows)
        feats = np.zeros((rows, 15), np.float32)
        feats[:, [1, 2]] = rng.integers(0, 2, (rows, 2))
        feats[:, [3, 5, 7, 9, 11, 13]] = rng.integers(0, 40, (rows, 6))
        feats[:, [4, 6, 8]] = rng.random((rows, 3)) * 300  # the averages
        probs = rng.random(15_000)[rng.integers(0, 15_000, rows)]
        amount = rng.integers(100, 1_000_000, rows)
    else:
        raise ValueError(holds)
    return BatchResult(
        tx_id=ids + 10_000_000_000 if holds in ("distinct", "served") else key,
        tx_datetime_us=key * 1_000 + 1_700_000_000_000_000,
        customer_id=key, terminal_id=key[::-1].copy(), amount_cents=amount,
        features=feats, probs=probs, latency_s=0.0, batch_index=3)


def _sink(tmp_path, kind):
    if kind == "local":
        return ParquetSink(str(tmp_path / "out"))
    return StoreParquetSink(
        S3Store("commerce", prefix="analyzed", client=FakeS3Client()))


def _part_bytes(sink) -> bytes:
    name = "part-00000003.parquet"
    if isinstance(sink, ParquetSink):
        assert os.listdir(sink.directory) == [name]  # the .tmp was renamed
        with open(os.path.join(sink.directory, name), "rb") as f:
            return f.read()
    assert sink.store.list("") == [name]
    return sink.store.get(name)


def _counters(kind):
    reg, label = get_registry(), {"local": "parquet",
                                  "store": "store_parquet"}[kind]
    return np.array([reg.counter(name, sink=label).value for name in (
        "rtfds_sink_plain_columns_total", "rtfds_sink_dict_columns_total",
        "rtfds_sink_rows_total", "rtfds_sink_bytes_total")])


@pytest.mark.parametrize("holds", ["distinct", "constant", "few", "served"])
@pytest.mark.parametrize("rows", [1, 1_000, 65_536])
@pytest.mark.parametrize("kind", ["local", "store"])
def test_part_file(tmp_path, monkeypatch, kind, rows, holds):
    # processed_at_us is the clock's reading: hold it still for the compare
    monkeypatch.setattr(sink_mod, "time", SimpleNamespace(
        time=lambda: 1_750_000_000.25, perf_counter=time.perf_counter))
    res = _result(rows, holds)
    want = _result_to_columns(res)
    sink = _sink(tmp_path, kind)
    before = _counters(kind)
    sink.append(res)
    counted = _counters(kind) - before

    # (i) every value and dtype on read-back
    got = sink.read_all()
    assert list(got) == list(want) and len(got) == N_COLUMNS
    for name, col in want.items():
        assert got[name].dtype == col.dtype, name
        assert got[name].tobytes() == col.tobytes(), name

    # (ii) the schema a reader sees is the one pyarrow's defaults write
    data = _part_bytes(sink)
    table = pa.table({k: pa.array(v) for k, v in want.items()})
    by_default = io.BytesIO()
    pq.write_table(table, by_default)
    mine = pq.ParquetFile(io.BytesIO(data))
    theirs = pq.ParquetFile(io.BytesIO(by_default.getvalue()))
    assert mine.schema_arrow.equals(theirs.schema_arrow, check_metadata=True)
    assert mine.schema.equals(theirs.schema)  # the Parquet types, too
    assert mine.metadata.format_version == theirs.metadata.format_version
    assert mine.metadata.num_rows == rows

    # (iii) chunk by chunk: plain where nearly all values differ, a
    # dictionary where they repeat; snappy and statistics everywhere
    assert mine.metadata.num_row_groups == 1
    group, plain = mine.metadata.row_group(0), []
    for j, (name, col) in enumerate(want.items()):
        chunk = group.column(j)
        assert chunk.path_in_schema == name
        assert chunk.compression == "SNAPPY", name
        assert chunk.is_stats_set and chunk.statistics.has_min_max, name
        assert chunk.statistics.min == col.min(), name
        assert chunk.statistics.max == col.max(), name
        width = WIDTH[chunk.physical_type]
        distinct = len(np.unique(col))
        if rows == 65_536 and distinct >= 10_000:
            # every value at full width, and beside them only the indices
            # of the rows the dictionary took before it was left: under
            # 2 % of an eight-byte column (the ids, amounts, probabilities)
            over = chunk.total_uncompressed_size - rows * width
            assert 0 <= over <= LIMIT // 2, name
            assert over <= 0.02 * rows * width or width == 4, name
            if chunk.has_dictionary_page:  # what it held when it was left
                assert (chunk.data_page_offset - chunk.dictionary_page_offset
                        <= LIMIT + WRITE_BATCH * width + 64), name
            plain.append(name)
        else:
            assert chunk.has_dictionary_page, name
            if distinct <= 7 and rows == 65_536:
                assert chunk.total_uncompressed_size < rows * width / 10, name
            elif distinct <= 7 and rows == 1_000:
                assert chunk.total_uncompressed_size < rows * width / 2, name
    if rows == 65_536:
        assert len(plain) == {"distinct": 20, "constant": 0, "few": 0,
                              "served": 9}[holds], plain
    else:
        assert plain == []  # under one look at the limit nothing falls back

    # (iv) and the file is no larger for it
    assert len(data) <= len(by_default.getvalue())

    # (v) the counters: 21 a part, split as the chunks are
    assert counted.tolist() == [len(plain), N_COLUMNS - len(plain), rows,
                                len(data)]
