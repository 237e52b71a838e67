"""Tier-1 perf smoke for get_json_object (regression tripwire, not a bench).

The round-5 profile had this op at three orders of magnitude below every
other kernel; the PR that introduced this file rebuilt the hot half of the
pipeline (adaptive host machine, numpy grammar walk, lazy float renders).
This smoke pins a *conservative* floor so a future change that quietly
re-introduces a pathological slowdown (e.g. a per-row python loop in the
machine, or an accidental one-hot gather on CPU) fails loudly in tier-1,
while normal CI jitter — a loaded box, a cold cache — cannot flake it:

- warm-up call first (compile + numpy allocator warm);
- best-of-3 timing (immune to one GC pause / scheduler hiccup);
- the floor sits ~15x under the measured rate on the dev box
  (~6-8 krows/s warm at this rectangle on the virtual CPU mesh).
"""

import time

from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.columnar.column import strings_from_bytes
from spark_rapids_jni_tpu.ops.get_json_object import (
    get_json_object,
    get_json_object_multiple_paths,
)

_FLOOR_ROWS_PER_S = 500.0
_ROWS = 2048


def _col():
    rows = [
        b'{"store": {"fruit": [{"weight": %d, "type": "apple"}, '
        b'{"weight": %d}], "book": "b%d"}, "k%d": %d.5}'
        % (i % 9, i % 7, i % 100, i % 3, i)
        for i in range(_ROWS)
    ]
    return strings_from_bytes(rows)


def test_single_path_throughput_floor():
    col = _col()
    with config.override(json_device_render=False):
        run = lambda: get_json_object(  # noqa: E731
            col, "$.store.fruit[*].weight").chars
        run()  # warm-up: compiles the bucket-shape tokenizer variants
        best = min(_timed(run) for _ in range(3))
    rate = _ROWS / best
    assert rate >= _FLOOR_ROWS_PER_S, (
        f"get_json_object fell to {rate:.0f} rows/s "
        f"(floor {_FLOOR_ROWS_PER_S}); the host pipeline has regressed "
        f"pathologically")


def test_multi_path_amortizes_tokenization():
    """4 paths over one column must cost well under 4 separate calls —
    the whole point of the multiple-paths entry.  Generous ceiling (3x a
    single call) so CI jitter cannot flake it; the bench tracks the real
    ratio (~1.2-1.7x)."""
    col = _col()
    paths = ["$.store.fruit[*].weight", "$.store.book", "$.k0",
             "$.store.fruit[0].type"]
    with config.override(json_device_render=False):
        single = lambda: get_json_object(col, paths[0]).chars  # noqa: E731
        multi = lambda: [  # noqa: E731
            c.chars for c in get_json_object_multiple_paths(col, paths)]
        single()
        multi()  # warm-up
        t_single = min(_timed(single) for _ in range(3))
        t_multi = min(_timed(multi) for _ in range(3))
    assert t_multi <= 3.0 * t_single + 0.05, (
        f"4-path multi call took {t_multi:.3f}s vs single {t_single:.3f}s "
        f"— tokenization is no longer being shared")


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
