"""Shared set-up of the benchmark's CPU tests: tiny cells on the CPU mesh."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil

from benchmark import cells

ROOT = cells.ROOT
CELLS = ("nds_sf10_1chip.q97_power", "nds_sf10_4chip.q97_power")
#: a test-size SF: key domains small enough that both sides share pairs
TINY = {"store_sales_rows": 3000, "catalog_sales_rows": 1500,
        "customer_rows": 50, "item_rows": 40}
#: stands in for benchmark/peaks.json on the CPU, which has no peaks
CPU_PEAKS = {"hbm_bytes_per_s": 1e9}


def tiny_cell(name: str, root: str = ROOT, **sizes) -> cells.Cell:
    cell = cells.load_cell(name, root)
    return dataclasses.replace(cell, config={**cell.config, **TINY, **sizes})


def copy_benchmark(dst: str) -> str:
    """A scratch root holding only BENCHMARK.json and benchmark/."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
