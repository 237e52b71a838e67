"""Split-planned parquet reading: the footer filter as a load-bearing
planner (io/parquet_read.py over io/parquet_footer.py).

Parity: NativeParquetJni.cpp:584 filter_groups / ParquetFooter.java:190-215
readAndFilter feeding the columnar reader.  These tests write a real
multi-row-group q97 fact file, split it by byte range two ways, and prove:
(a) the splits partition the row groups exactly, (b) each split's q97
partial verifies against the host oracle on that split's rows, and
(c) the pruned money columns are never handed to the decoder.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu.io import (
    ParquetFooter,
    StructElement,
    ValueElement,
    plan_byte_splits,
    plan_split,
    read_split,
)
from spark_rapids_jni_tpu.io.parquet_read import footer_bytes
from spark_rapids_jni_tpu.models.tpcds import write_q97_parquet


@pytest.fixture(scope="module")
def q97_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("nds_parquet")
    return write_q97_parquet(str(d), sf=0.002, seed=7, rows_per_group=1024)


def _keys_schema(prefix: str) -> StructElement:
    return (StructElement.builder()
            .add_child(f"{prefix}_customer_sk", ValueElement())
            .add_child(f"{prefix}_item_sk", ValueElement())
            .build())


def test_byte_splits_partition_row_groups(q97_files):
    """Every row group lands in exactly one byte-range split (the midpoint
    rule): two executors reading two splits see each row exactly once."""
    import pyarrow.parquet as pq

    store_path, _ = q97_files
    n_groups = pq.ParquetFile(store_path).num_row_groups
    assert n_groups >= 3, "fixture must be multi-row-group to mean anything"

    fb = footer_bytes(store_path)
    seen = []
    for off, length in plan_byte_splits(store_path, 2):
        seen.append(ParquetFooter.split_group_indexes(fb, off, length))
    assert all(g for g in seen), "both splits must get work"
    flat = [i for g in seen for i in g]
    assert sorted(flat) == list(range(n_groups))
    assert len(set(flat)) == len(flat), "no row group may appear twice"


def test_plan_prunes_columns(q97_files):
    store_path, _ = q97_files
    (off, length) = plan_byte_splits(store_path, 1)[0]
    plan = plan_split(store_path, off, length, _keys_schema("ss"))
    assert plan.columns == ["ss_customer_sk", "ss_item_sk"]


def test_pruned_columns_never_materialized(q97_files, monkeypatch):
    """The decoder is only ever asked for the surviving projection — the
    money columns cannot be materialized even transiently."""
    import pyarrow.parquet as pq

    store_path, _ = q97_files
    asked = []
    orig = pq.ParquetFile.read_row_group

    def spy(self, i, columns=None, **kw):
        asked.append(list(columns or []))
        return orig(self, i, columns=columns, **kw)

    monkeypatch.setattr(pq.ParquetFile, "read_row_group", spy)
    off, length = plan_byte_splits(store_path, 1)[0]
    out = read_split(store_path, off, length, _keys_schema("ss"))
    assert set(out) == {"ss_customer_sk", "ss_item_sk"}
    assert asked and all(
        cols == ["ss_customer_sk", "ss_item_sk"] for cols in asked)


def test_each_split_q97_partial_verifies(q97_files):
    """One file, split two ways: each split's q97 partial (vs the catalog
    file read whole) matches the host set oracle on exactly that split's
    rows, and the two splits together cover the whole file."""
    import jax

    from spark_rapids_jni_tpu.models import run_distributed_q97
    from spark_rapids_jni_tpu.parallel import make_mesh

    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    store_path, catalog_path = q97_files
    cat = read_split(catalog_path, *plan_byte_splits(catalog_path, 1)[0],
                     schema=_keys_schema("cs"), as_numpy=True)
    catalog = (cat["cs_customer_sk"][0].astype(np.int32),
               cat["cs_item_sk"][0].astype(np.int32))
    c_set = set(zip(catalog[0].tolist(), catalog[1].tolist()))

    total_rows = 0
    for off, length in plan_byte_splits(store_path, 2):
        part = read_split(store_path, off, length,
                          schema=_keys_schema("ss"), as_numpy=True)
        store = (part["ss_customer_sk"][0].astype(np.int32),
                 part["ss_item_sk"][0].astype(np.int32))
        total_rows += len(store[0])
        out = run_distributed_q97(mesh, store, catalog)
        s_set = set(zip(store[0].tolist(), store[1].tolist()))
        want = (len(s_set - c_set), len(c_set - s_set), len(s_set & c_set))
        got = (int(out.store_only), int(out.catalog_only), int(out.both))
        assert got == want, f"split at {off}: {got} != {want}"

    import pyarrow.parquet as pq

    assert total_rows == pq.ParquetFile(store_path).metadata.num_rows


@pytest.mark.slow
def test_nds_harness_input_mode(q97_files, tmp_path, capsys):
    """The NDS harness end to end in --input mode: q97 over parquet fact
    tables whose reads were planned by the footer filter, verified."""
    import json
    import os

    from spark_rapids_jni_tpu.models import nds_harness

    input_dir = os.path.dirname(q97_files[0])
    rc = nds_harness.main(["--sf", "0.002", "--input", input_dir,
                           "--splits", "2", "--verify"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["queries"]["q97"]["verified"] is True
    assert out["splits_per_file"] == 2


def test_oversubscribed_splits_still_partition(q97_files):
    """More splits than bytes must never produce a negative-length split
    (which would read as 'filtering disabled' and double-count groups):
    the groups are still partitioned exactly once."""
    import pyarrow.parquet as pq

    store_path, _ = q97_files
    n_groups = pq.ParquetFile(store_path).num_row_groups
    fb = footer_bytes(store_path)
    # extreme oversubscription: every split must still have positive length
    assert all(ln > 0 for _, ln in plan_byte_splits(store_path, 10**6))
    # moderate oversubscription (>> groups): groups partition exactly once
    splits = plan_byte_splits(store_path, 64)
    flat = [i for off, ln in splits
            for i in ParquetFooter.split_group_indexes(fb, off, ln)]
    assert sorted(flat) == list(range(n_groups))


def test_iter_split_batches_row_group_chunks(q97_files):
    """The chunked scan yields one batch per surviving row group, covers
    every row exactly once across splits, and matches the one-shot
    read_split materialization."""
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.io import iter_split_batches

    store_path, _ = q97_files
    pf = pq.ParquetFile(store_path)
    group_rows = [pf.metadata.row_group(i).num_rows
                  for i in range(pf.num_row_groups)]

    all_rows = []
    n_batches = 0
    for off, length in plan_byte_splits(store_path, 2):
        split_rows = []
        for batch in iter_split_batches(store_path, off, length,
                                        _keys_schema("ss"), as_numpy=True):
            n_batches += 1
            cust = np.asarray(batch["ss_customer_sk"][0])
            item = np.asarray(batch["ss_item_sk"][0])
            assert len(cust) <= max(group_rows), \
                "a batch must never exceed one row group"
            split_rows.extend(zip(cust.tolist(), item.tolist()))
        whole = read_split(store_path, off, length, _keys_schema("ss"),
                           as_numpy=True)
        want = list(zip(whole["ss_customer_sk"][0].tolist(),
                        whole["ss_item_sk"][0].tolist()))
        assert split_rows == want, "chunked == one-shot, in order"
        all_rows.extend(split_rows)
    assert n_batches == pf.num_row_groups
    assert len(all_rows) == pf.metadata.num_rows, "each row exactly once"


def test_q97_parquet_chunks_exactly_once_and_null_free(q97_files, tmp_path):
    """The harness chunk source covers both sides completely (row-group
    granularity) and drops NULL-keyed rows."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.models.nds_harness import q97_parquet_chunks

    input_dir = __import__("os").path.dirname(q97_files[0])
    per_side = {"store": 0, "catalog": 0}
    for side, cust, item in q97_parquet_chunks(input_dir, 3):
        assert cust.dtype == np.int32 and item.dtype == np.int32
        per_side[side] += len(cust)
    assert per_side["store"] == pq.ParquetFile(
        q97_files[0]).metadata.num_rows
    assert per_side["catalog"] == pq.ParquetFile(
        q97_files[1]).metadata.num_rows

    # null keys dropped at the chunk source
    for name, prefix in (("store_sales", "ss"), ("catalog_sales", "cs")):
        table = pa.table({
            f"{prefix}_customer_sk": pa.array([1, None, 3, 4], pa.int32()),
            f"{prefix}_item_sk": pa.array([10, 20, None, 40], pa.int32()),
        })
        pq.write_table(table, str(tmp_path / f"{name}.parquet"),
                       row_group_size=2)
    rows = {"store": set(), "catalog": set()}
    for side, cust, item in q97_parquet_chunks(str(tmp_path), 2):
        rows[side] |= set(zip(cust.tolist(), item.tolist()))
    assert rows["store"] == rows["catalog"] == {(1, 10), (4, 40)}


@pytest.mark.slow
def test_q97_streamed_from_parquet_matches_oracle(q97_files):
    """VERDICT r4 #4 done criterion: q97 out-of-core FROM multi-row-group
    parquet, footer-planned across 2 simulated executors (byte-range
    splits), each row seen exactly once, verified — the scan partitions
    by footer, the disk grace hash reunifies the buckets."""
    import os
    import tempfile

    import jax

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models.nds_harness import q97_parquet_chunks
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.models.streaming import run_streaming_q97
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    store_path, catalog_path = q97_files
    input_dir = os.path.dirname(store_path)
    whole = {}
    for path, prefix in ((store_path, "ss"), (catalog_path, "cs")):
        part = read_split(path, *plan_byte_splits(path, 1)[0],
                          schema=_keys_schema(prefix), as_numpy=True)
        whole[prefix] = (part[f"{prefix}_customer_sk"][0].astype(np.int32),
                         part[f"{prefix}_item_sk"][0].astype(np.int32))
    want = q97_host_oracle(whole["ss"], whole["cs"])

    mesh = make_mesh((len(jax.devices()), 1))
    gov = MemoryGovernor.initialize()
    host_budget = BudgetedResource(gov, 1 << 30, is_cpu=True)
    try:
        with tempfile.TemporaryDirectory() as td:
            counts, verified, stats = run_streaming_q97(
                mesh, q97_parquet_chunks(input_dir, 2),
                tmpdir=td, n_buckets=8, host_budget=host_budget,
                task_id=9, verify=True)
    finally:
        MemoryGovernor.shutdown()
    assert verified is True
    assert counts == want
    assert stats["rows_in"] == len(whole["ss"][0]) + len(whole["cs"][0])


@pytest.mark.slow
def test_nds_harness_input_streamed_mode(q97_files, capsys):
    """--input composes with --stream-chunk-rows: q97 runs out-of-core
    from footer-planned parquet row groups, verified end to end."""
    import json
    import os

    from spark_rapids_jni_tpu.models import nds_harness

    input_dir = os.path.dirname(q97_files[0])
    rc = nds_harness.main(["--sf", "0.002", "--input", input_dir,
                           "--splits", "2", "--stream-chunk-rows", "2000",
                           "--buckets", "4", "--verify"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["queries"]["q97"]["verified"] is True
    assert out["queries"]["q97"]["streamed"]["n_buckets"] == 4
    assert out["queries"]["q5"]["verified"] is True
    assert "streamed" in out["queries"]["q5"]


def test_parquet_decimal_roundtrip_with_nulls(tmp_path):
    """Parquet DECIMAL(p, s) written then read back through the split
    reader decodes to the framework's unscaled storage — int32/int64
    Columns for p<=9/p<=18, Decimal128Column above — with validity intact
    (VERDICT r4 #7; NativeParquetJni.cpp:102 decimal Tag tree parity)."""
    import decimal as pydec

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu import columnar as c
    from spark_rapids_jni_tpu.io import StructElement, ValueElement

    def dec(s):
        return None if s is None else pydec.Decimal(s)

    small = [dec("12345.67"), None, dec("-0.01"), dec("99999.99")]
    mid = [None, dec("9999999999999.99"), dec("-1234567890.05"), dec("0.00")]
    big = [dec("9" * 28 + "." + "9" * 10), dec("-" + "8" * 20 + ".5"),
           None, dec("0.0000000001")]
    table = pa.table({
        "m_small": pa.array(small, pa.decimal128(7, 2)),
        "m_mid": pa.array(mid, pa.decimal128(15, 2)),
        "m_big": pa.array(big, pa.decimal128(38, 10)),
        "k": pa.array([1, 2, 3, 4], pa.int32()),
    })
    path = str(tmp_path / "money.parquet")
    pq.write_table(table, path, row_group_size=2)

    schema = (StructElement.builder()
              .add_child("m_small", ValueElement())
              .add_child("m_mid", ValueElement())
              .add_child("m_big", ValueElement())
              .build())
    out = {}
    for off, length in plan_byte_splits(path, 2):
        part = read_split(path, off, length, schema)
        for name, col in part.items():
            out.setdefault(name, []).append(col)

    def unscaled(vals, scale):
        # exact scaleb: the default Decimal context would round 38-digit
        # values to 28 significant digits
        with pydec.localcontext() as ctx:
            ctx.prec = 80
            return [None if v is None else int(v.scaleb(scale))
                    for v in vals]

    got_small = [v for col in out["m_small"] for v in col.to_list()]
    assert isinstance(out["m_small"][0], c.Column)
    assert out["m_small"][0].dtype.kind == c.Kind.DECIMAL32
    assert out["m_small"][0].dtype.scale == 2
    assert got_small == unscaled(small, 2)

    got_mid = [v for col in out["m_mid"] for v in col.to_list()]
    assert out["m_mid"][0].dtype.kind == c.Kind.DECIMAL64
    assert got_mid == unscaled(mid, 2)

    assert isinstance(out["m_big"][0], c.Decimal128Column)
    assert out["m_big"][0].dtype.precision == 38
    got_big = [v for col in out["m_big"] for v in col.unscaled_to_list()]
    assert got_big == unscaled(big, 10)


def test_harness_parquet_read_excludes_null_keys(tmp_path):
    """NULL join keys in parquet must be excluded from q97, not counted
    as key 0 (q97_host_oracle non-null semantics)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.models.nds_harness import (
        _q97_tables_from_parquet,
    )

    for name, prefix in (("store_sales", "ss"), ("catalog_sales", "cs")):
        table = pa.table({
            f"{prefix}_customer_sk": pa.array([1, None, 3, 4], pa.int32()),
            f"{prefix}_item_sk": pa.array([10, 20, None, 40], pa.int32()),
        })
        pq.write_table(table, str(tmp_path / f"{name}.parquet"),
                       row_group_size=2)
    store, catalog = _q97_tables_from_parquet(str(tmp_path), 2)
    for cust, item in (store, catalog):
        assert len(cust) == 2, "rows with any NULL key must be dropped"
        assert set(zip(cust.tolist(), item.tolist())) == {(1, 10), (4, 40)}
        assert 0 not in cust.tolist()
