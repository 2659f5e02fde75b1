"""Pallas fused filter→group-by scan (single-chip throughput push).

Exactness contract: the Pallas kernel (run here in interpret mode — tier-1
is JAX_PLATFORMS=cpu) must match the XLA segmented path bit-for-bit for
every integer kind it claims (count / int_sum / int64_sum), including the
in-register word-mask and dict-code-predicate fusion and the row-padding
tail.  The engine-level tests prove plan-time routing: the same query
returns identical rows under backend=xla and backend=interpret, the
word-fused dense kernel really rides the range-index bitmap, the sparse
cross-launch merge happens ON DEVICE (trace spans), and the
double-buffered launch pipeline is deterministic across depths."""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu import ops
from pinot_tpu.ops import pallas_scan, segmented
from pinot_tpu.parallel.engine import DistributedEngine
from pinot_tpu.parallel.stacked import StackedTable
from pinot_tpu.segment import packing
from pinot_tpu.spi.config import IndexingConfig, TableConfig
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema
from pinot_tpu.sql.parser import parse_query


def _one_table(kind, values, mask, codes, num_groups):
    """The entry through the single-table API, one scan of its own."""
    if kind == "count":
        return ops.group_count(mask, codes, num_groups)
    if kind == "f32_sumsq":
        return ops.group_sum_sq(values, mask, codes, num_groups)
    return ops.group_sum(values, mask, codes, num_groups)


def _reference(entries, codes, num_groups):
    return [np.asarray(_one_table(k, v, m, codes, num_groups), np.float64) for k, v, m, _ in entries]


def _entries(rng, n):
    """One entry per supported kind, with signs and widths that exercise
    every limb column (int8 negative, int32 full range, int64 past int32).
    int64 magnitudes stay under 2^39 so worst-case group sums remain inside
    the f64 integer-exact window — the same output contract as the XLA
    path, whose tables are also f64."""
    m = lambda: rng.random(n) < 0.8
    return [
        ("count", jnp.zeros((n,), jnp.int32), jnp.asarray(m()), None),
        ("int_sum", jnp.asarray(rng.integers(-120, 120, n).astype(np.int8)), jnp.asarray(m()), (1, True)),
        ("int_sum", jnp.asarray(rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)), jnp.asarray(m()), (4, True)),
        ("int64_sum", jnp.asarray(rng.integers(-(2**39), 2**39, n).astype(np.int64)), jnp.asarray(m()), None),
    ]


def _mask(rng, n, p=0.8):
    return jnp.asarray(rng.random(n) < p)


def _words(bits):
    """A row mask as range-index bitmap words (bit r of word w = row 32 w + r)."""
    return jnp.asarray(
        np.packbits(bits.reshape(-1, 32), axis=1, bitorder="little").view(np.uint32).reshape(-1)
    )


def _every_kind(num_groups, n):
    def build(rng):
        return _entries(rng, n), rng.integers(0, num_groups, n), num_groups, {}

    return build


def _q2_count_and_revenue(rng):
    """SSB Q2.x in cell 1: 7,000 slots, COUNT and an unsigned three-limb SUM
    (kernel l4_h112: the stack is the wider one-hot's partner)."""
    n = 40_000
    v = rng.integers(0, 2**24, n).astype(np.int32)
    m = _mask(rng, n)
    entries = [("count", None, m, None), ("int_sum", jnp.asarray(v), m, (3, False))]
    return entries, rng.integers(0, 7000, n), 7000, {}


def _q4_signed_difference(slots):
    """SSB Q4.x: SUM(lo_revenue - lo_supplycost), four limbs and the
    negatives' count beside COUNT (l6_h72 at 4,375 slots; at 175 slots
    l6_h8, where the table's own one-hot is the narrower and carries the
    limbs)."""

    def build(rng):
        n = 40_000
        v = (rng.integers(0, 2**24, n) - rng.integers(0, 2**25, n)).astype(np.int32)
        m = _mask(rng, n, 0.6)
        entries = [("count", None, m, None), ("int_sum", jnp.asarray(v), m, (4, True))]
        return entries, rng.integers(0, slots, n), slots, {}

    return build


def _lone_count(slots):
    def build(rng):
        n = 33_000
        return [("count", None, _mask(rng, n), None)], rng.integers(0, slots, n), slots, {}

    return build


def _int64_negative(rng):
    """Signed-magnitude limbs of an int64 column that is mostly negative,
    six limbs wide as a star-tree level's sums are (l7_h72)."""
    n = 40_000
    v = -rng.integers(0, 2**47, n).astype(np.int64) + rng.integers(0, 2**20, n)
    m = _mask(rng, n)
    entries = [("count", None, m, None), ("int64_sum", jnp.asarray(v), m, 6)]
    return entries, rng.integers(0, 4375, n), 4375, {}


def _masks_of_their_own(rng):
    """COUNT(*) FILTER (...) beside a SUM under another filter: each limb
    column of the stack carries its entry's mask, not a shared one."""
    n = 4096 * 3
    v = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    entries = [
        ("count", None, _mask(rng, n, 0.2), None),
        ("int_sum", jnp.asarray(v), _mask(rng, n, 0.9), (4, True)),
        ("count", None, _mask(rng, n, 0.5), None),
    ]
    return entries, rng.integers(0, 300, n), 300, {}


def _packed_key_words_and_pred(rng):
    """The key read from its bit-packed forward index, the filter from
    bitmap words and a dictionary-code range, all unpacked in-register."""
    n, groups, bits = 2 * packing.BLOCK_ROWS + 4096, 200, 8
    codes = rng.integers(0, groups, n)
    v = rng.integers(0, 2**16, n).astype(np.int32)
    m = _mask(rng, n)
    pc = rng.integers(0, 60, n).astype(np.int32)
    wbits = rng.random(n) < 0.5
    entries = [("count", None, m, None), ("int_sum", jnp.asarray(v), m, (2, False))]
    kwargs = dict(
        mask_words=_words(wbits),
        code_pred=(jnp.asarray(pc), 10, 40),
        codes_packed=(jnp.asarray(packing.pack_codes(codes.astype(np.uint32), bits)), bits),
    )
    return entries, codes, groups, kwargs


def _ragged_rows(rng):
    """Neither a tile, a chunk nor 32 rows divide the row count."""
    n = packing.BLOCK_ROWS + 4096 + 77
    v = rng.integers(-(2**15), 2**15, n).astype(np.int16)
    m = _mask(rng, n)
    entries = [("count", None, m, None), ("int_sum", jnp.asarray(v), m, (2, True))]
    return entries, rng.integers(0, 4375, n), 4375, {}


def _limb_bounds(n):
    """Every byte 255, every row in ONE group: a chunk's f32 dot reaches
    255 x 4,096 (< 2^24) and, past 2^23 rows, a super-segment's int32 sum
    255 x 2^23 (< 2^31) before the next one starts."""

    def build(rng):
        ones = jnp.ones((n,), bool)
        entries = [
            ("count", None, ones, None),
            ("int_sum", jnp.full((n,), 0xFFFFFF, jnp.int32), ones, (3, False)),
            ("int_sum", jnp.full((n,), -1, jnp.int32), ones, (4, True)),
        ]
        return entries, np.full(n, 5), 70, {}

    return build


# the seed's grid (every kind, four row counts, three table widths), then the
# plans the benchmark's cells run and the edges of the stacked operand
EXACT_CASES = {
    f"every_kind_g{g}_n{n}": _every_kind(g, n)
    for g in (1, 7, 300)
    for n in (32, 4096, 4096 * 2 + 32, 1000)  # 1000: pad tail
}
EXACT_CASES.update(
    l4_h112_count_and_three_limbs=_q2_count_and_revenue,
    l6_h72_signed_difference=_q4_signed_difference(4375),
    l6_h8_signed_difference=_q4_signed_difference(175),
    l1_h8_lone_count=_lone_count(175),
    l1_h112_lone_count=_lone_count(7000),
    l7_h72_int64_negative=_int64_negative,
    masks_of_their_own=_masks_of_their_own,
    packed_key_words_and_pred=_packed_key_words_and_pred,
    ragged_rows=_ragged_rows,
    limb_bounds_one_chunk=_limb_bounds(4096),
    limb_bounds_past_a_super_segment=_limb_bounds((1 << 23) + 4096),
)


@pytest.mark.parametrize("case", sorted(EXACT_CASES))
def test_exactness_vs_xla(rng, case):
    """Bit for bit against the XLA scan (the same filter handed over as
    plain row masks) and, entry by entry, the single-table API."""
    entries, codes, num_groups, kwargs = EXACT_CASES[case](rng)
    codes = jnp.asarray(np.asarray(codes).astype(np.int32))
    got = pallas_scan.fused_group_tables_pallas(
        entries, codes, num_groups, interpret=True, **kwargs
    )
    n = int(codes.shape[0])
    rows = np.ones(n, bool)
    if "mask_words" in kwargs:
        rows &= np.asarray(segmented.unpack_bitmap_words(kwargs["mask_words"], n))
    if "code_pred" in kwargs:
        pc, lo, hi = kwargs["code_pred"]
        rows &= (np.asarray(pc) >= lo) & (np.asarray(pc) < hi)
    plain = [(k, v, jnp.asarray(np.asarray(m) & rows), lp) for k, v, m, lp in entries]
    xla = segmented.fused_group_tables(plain, codes, num_groups)
    for g, x, r in zip(got, xla, _reference(plain, codes, num_groups)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(g), r)


def test_word_mask_and_code_pred_fusion(rng):
    """Packed bitmap words + dict-code range predicate, fused in-register,
    must equal the same filter applied as an unpacked row mask."""
    n = 4096 * 3 + 32
    entries = _entries(rng, n)
    codes = jnp.asarray(rng.integers(0, 50, n).astype(np.int32))
    words = _words(rng.random(n) < 0.5)
    lo, hi = 10, 40
    got = pallas_scan.fused_group_tables_pallas(
        entries, codes, 50, mask_words=words, code_pred=(codes, lo, hi), interpret=True
    )
    unpacked = np.asarray(segmented.unpack_bitmap_words(words, n))
    pred = (np.asarray(codes) >= lo) & (np.asarray(codes) < hi)
    ref_entries = [
        (k, v, jnp.asarray(np.asarray(m) & unpacked & pred), lp)
        for k, v, m, lp in entries
    ]
    ref = _reference(ref_entries, codes, 50)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), r)


def test_word_mask_requires_alignment(rng):
    n = 40  # not a multiple of 32
    entries = [("count", jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool), None)]
    with pytest.raises(ValueError):
        pallas_scan.fused_group_tables_pallas(
            entries,
            jnp.zeros((n,), jnp.int32),
            4,
            mask_words=jnp.zeros((2,), jnp.uint32),
            interpret=True,
        )


def test_pallas_supported_gates():
    n = 64
    count = ("count", jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool), None)
    fsum = ("f32_sum", jnp.zeros((n,), jnp.float32), jnp.ones((n,), bool), None)
    assert pallas_scan.pallas_supported([count], 16)
    assert not pallas_scan.pallas_supported([count, fsum], 16)  # float kind
    assert not pallas_scan.pallas_supported([count], 0)
    assert not pallas_scan.pallas_supported([count], segmented._MATMUL_MAX_GROUPS + 1)
    wide = ("int_sum", jnp.zeros((n,), jnp.int64), jnp.ones((n,), bool), None)
    assert not pallas_scan.pallas_supported([wide], 16)  # int_sum must be <=4 bytes


def test_scan_backend_env(monkeypatch):
    monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
    ops.scan_backend.cache_clear()
    assert ops.scan_backend() == "interpret"
    # a forced backend that cannot be honoured raises; it never degrades
    monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", "pallas")
    ops.scan_backend.cache_clear()
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        ops.scan_backend()
    monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", "mosaic")
    ops.scan_backend.cache_clear()
    with pytest.raises(ValueError, match="expected one of"):
        ops.scan_backend()
    monkeypatch.delenv("PINOT_TPU_SCAN_BACKEND")
    ops.scan_backend.cache_clear()
    assert ops.scan_backend() == "xla"  # CPU default: Pallas only on TPU
    ops.scan_backend.cache_clear()


def test_merge_sparse_tables_folds_duplicates():
    E = int(pallas_scan.SPARSE_EMPTY_KEY)
    uniq = jnp.asarray(np.array([5, 2, E, 2, 9, E, 5, 1], np.int64))
    s = jnp.asarray(np.array([10, 1, 0, 2, 7, 0, 5, 3], np.float64))
    c = jnp.asarray(np.array([2, 1, 0, 1, 1, 0, 1, 1], np.float64))
    keys, tables = pallas_scan.merge_sparse_tables(
        uniq, [{"sum": s, "count": c}], 8, [{"sum": "add", "count": "add"}]
    )
    keys, t = np.asarray(keys), {f: np.asarray(v) for f, v in tables[0].items()}
    present = keys != E
    assert list(keys[present]) == [1, 2, 5, 9]
    np.testing.assert_array_equal(t["sum"][present], [3, 3, 15, 7])
    np.testing.assert_array_equal(t["count"][present], [1, 2, 3, 1])


def test_merge_sparse_tables_min_max_identities():
    """Empty slots must not poison MIN/MAX (identity padding on device)."""
    E = int(pallas_scan.SPARSE_EMPTY_KEY)
    uniq = jnp.asarray(np.array([3, E, 3, 7], np.int64))
    mn = jnp.asarray(np.array([4.0, 0.0, -2.0, 9.0]))
    mx = jnp.asarray(np.array([4.0, 0.0, -2.0, 9.0]))
    c = jnp.asarray(np.array([1.0, 0.0, 1.0, 1.0]))
    keys, tables = pallas_scan.merge_sparse_tables(
        uniq, [{"min": mn, "max": mx, "count": c}], 4,
        [{"min": "min", "max": "max", "count": "add"}],
    )
    keys, t = np.asarray(keys), {f: np.asarray(v) for f, v in tables[0].items()}
    present = keys != E
    assert list(keys[present]) == [3, 7]
    np.testing.assert_array_equal(t["min"][present], [-2.0, 9.0])
    np.testing.assert_array_equal(t["max"][present], [4.0, 9.0])


def test_merge_sparse_tables_order_trim():
    """ORDER BY sum DESC LIMIT 2 keeps the top-2 groups, emitted in
    ascending key order (executor decode contract)."""
    E = int(pallas_scan.SPARSE_EMPTY_KEY)
    uniq = jnp.asarray(np.array([5, 2, E, 2, 9, E, 5, 1], np.int64))
    s = jnp.asarray(np.array([10, 1, 0, 2, 7, 0, 5, 3], np.float64))
    c = jnp.asarray(np.array([2, 1, 0, 1, 1, 0, 1, 1], np.float64))
    keys, tables = pallas_scan.merge_sparse_tables(
        uniq, [{"sum": s, "count": c}], 2,
        [{"sum": "add", "count": "add"}], order_spec=(0, "sum", False),
    )
    keys, t = np.asarray(keys), {f: np.asarray(v) for f, v in tables[0].items()}
    assert list(keys) == [5, 9]  # sums 15 and 7: the DESC top-2, key-ascending
    np.testing.assert_array_equal(t["sum"], [15, 7])


# ---------------------------------------------------------------------------
# engine-level routing
# ---------------------------------------------------------------------------

N = 1245 * 8


def _bench_shaped_table(eng, *, seed=3):
    """A lineorder-shaped table: dict-encoded filter column with a range
    index, so the whole WHERE compiles to one plain bitmap and the dense
    kernel takes the word-fused path."""
    rng = np.random.default_rng(seed)
    schema = Schema(
        "lineorder",
        [
            FieldSpec("lo_orderdate", DataType.INT),
            FieldSpec("lo_quantity", DataType.INT),
            FieldSpec("g", DataType.STRING),
            FieldSpec("lo_revenue", DataType.LONG, role=FieldRole.METRIC),
        ],
    )
    data = {
        "lo_orderdate": (19920101 + rng.integers(0, 37, N)).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, N).astype(np.int32),
        "g": np.asarray([f"g{i}" for i in rng.integers(0, 7, N)]),
        "lo_revenue": rng.integers(-(10**9), 10**9, N).astype(np.int64),
    }
    cfg = TableConfig(
        "lineorder", indexing=IndexingConfig(range_index_columns=["lo_quantity"])
    )
    eng.register_table(
        "lineorder",
        StackedTable.build(schema, data, eng.num_devices, table_config=cfg),
    )
    return data


DENSE_Q = (
    "SELECT lo_orderdate, SUM(lo_revenue), COUNT(*) FROM lineorder "
    "WHERE lo_quantity < 25 GROUP BY lo_orderdate LIMIT 2500"
)


def _with_backend(monkeypatch, backend, **eng_kwargs):
    monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", backend)
    ops.scan_backend.cache_clear()
    eng = DistributedEngine(**eng_kwargs)
    _bench_shaped_table(eng)
    return eng


@pytest.fixture(autouse=True)
def _reset_backend_cache():
    yield
    ops.scan_backend.cache_clear()


def test_engine_word_fused_dense_routing(monkeypatch):
    """The headline group-by rides the range-index bitmap on both backends and
    returns identical rows, exact vs a pure-numpy reference."""
    rows = {}
    for be in ("xla", "interpret"):
        eng = _with_backend(monkeypatch, be)
        ctx = parse_query(DENSE_Q)
        plan = eng._plan(ctx, eng.tables["lineorder"])
        assert plan.row_sharded_params, "filter must ship bitmap words"
        r = eng.execute(ctx)
        assert ("lo_quantity", "range") in list(r.stats.filter_index_uses)
        rows[be] = r.rows
    assert rows["xla"] == rows["interpret"]

    data = _bench_shaped_table(DistributedEngine())  # same seed: same rows
    mask = data["lo_quantity"] < 25
    ref = {}
    for d, rv in zip(data["lo_orderdate"][mask], data["lo_revenue"][mask]):
        s, c = ref.get(int(d), (0, 0))
        ref[int(d)] = (s + int(rv), c + 1)
    got = {int(r[0]): (int(r[1]), int(r[2])) for r in rows["interpret"]}
    assert got == ref


def test_engine_backend_in_plan_cache_key(monkeypatch):
    """Switching backend must not reuse a plan traced for the other one."""
    eng = _with_backend(monkeypatch, "xla")
    ctx = parse_query(DENSE_Q)
    p_xla = eng._plan(ctx, eng.tables["lineorder"])
    monkeypatch.setenv("PINOT_TPU_SCAN_BACKEND", "interpret")
    ops.scan_backend.cache_clear()
    p_int = eng._plan(ctx, eng.tables["lineorder"])
    assert p_xla is not p_int


# maxDenseGroups=2 forces the sparse (fixed-slot hash table) plan at low
# cardinality, same idiom as test_sparse_groupby.py
SPARSE_Q = (
    "SET maxDenseGroups = 2; SELECT g, SUM(lo_revenue), COUNT(*) FROM lineorder "
    "GROUP BY g ORDER BY g LIMIT 10"
)
SPARSE_ORDER_Q = (
    "SET maxDenseGroups = 2; SELECT g, SUM(lo_revenue) FROM lineorder GROUP BY g "
    "ORDER BY SUM(lo_revenue) DESC LIMIT 3"
)


@pytest.mark.parametrize("query", [SPARSE_Q, SPARSE_ORDER_Q])
def test_sparse_merge_on_device_across_batches(query):
    """Macro-batched sparse group-by combines partial tables in-graph: the
    trace shows a device merge span and NO host merge, and rows match the
    single-launch engine exactly (including the ORDER BY ... LIMIT trim)."""
    base = DistributedEngine()
    _bench_shaped_table(base)
    eng = DistributedEngine(launch_bytes=4096)  # force several launches
    _bench_shaped_table(eng)

    ctx = parse_query(query)
    plan = eng._plan(ctx, eng.tables["lineorder"])
    assert plan.kind == "groupby_sparse"
    assert plan.sparse_merge_fn is not None
    assert len(plan.batch_offsets) >= 2, "budget must force macro-batching"

    ctx.options["trace"] = True
    r = eng.execute(ctx)
    spans = json.dumps(r.stats.trace)
    assert "sparse_merge:device" in spans
    assert "sparse_merge:host" not in spans
    assert r.rows == base.query(query).rows


def test_pipeline_depth_determinism(monkeypatch):
    """Double-buffered launches (depth>1) must be byte-identical to the
    sequential depth-1 schedule for every query kind."""
    rows = {}
    for depth in (1, 3):
        monkeypatch.setenv("PINOT_TPU_PIPELINE_DEPTH", str(depth))
        eng = DistributedEngine(launch_bytes=4096)
        assert eng.pipeline_depth == depth
        _bench_shaped_table(eng)
        rows[depth] = [eng.query(q).rows for q in (DENSE_Q, SPARSE_Q, SPARSE_ORDER_Q)]
    assert rows[1] == rows[3]
