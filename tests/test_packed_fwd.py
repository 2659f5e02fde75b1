"""Bit-packed forward indexes (segment/packing.py): lane-width selection,
the block-planar lane layout, pack/unpack round-trips (numpy, trace-level
and the Pallas kernel's in-register key unpack), segment build→save→load
parity across lane widths and boundary cardinalities, device shipping of
packed words, the stacked-table twin, and the backward-compat paths (a
pre-packing segment, and one written in the interleaved lane layout)."""
import dataclasses

import numpy as np
import pytest

from pinot_tpu.segment import packing
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.segment import BUILDER_VERSION, ImmutableSegment
from pinot_tpu.spi.schema import DataType, FieldRole, FieldSpec, Schema


def _dict_schema(nullable=False):
    return Schema(
        "t",
        [
            FieldSpec("k", DataType.STRING, nullable=nullable),
            FieldSpec("v", DataType.LONG, role=FieldRole.METRIC),
        ],
    )


def _dict_data(n, card, seed=0, null_rate=0.0):
    assert n >= card
    rng = np.random.default_rng(seed)
    # every dictionary id appears at least once: boundary-cardinality tests
    # need the EXACT cardinality, not a random subset
    ids = np.concatenate([np.arange(card), rng.integers(0, card, n - card)])
    rng.shuffle(ids)
    vals = np.array([f"k{i:06d}" for i in ids], dtype=object)
    if null_rate:
        vals[rng.random(n) < null_rate] = None
    return {"k": vals, "v": rng.integers(0, 1000, n)}


class TestLaneSelection:
    @pytest.mark.parametrize(
        "card,bits",
        [(1, 4), (16, 4), (17, 8), (256, 8), (257, 16), (65536, 16), (65537, 32)],
    )
    def test_boundary_cardinalities(self, card, bits):
        assert packing.lane_bits(card) == bits


B = packing.BLOCK_ROWS
# tail-word cases of the old layout, then the block bounds and a segment
ROW_COUNTS = [1, 7, 32, 1000, B - 1, B, B + 1, 1_500_000]


def _codes(bits, n, seed=0):
    return np.random.default_rng([bits, n, seed]).integers(0, 1 << bits, n).astype(np.uint32)


def _pack_interleaved(codes, bits):
    """The layout of builder version 2 (lane l of word w covers row
    w * f + l), kept here to write segments as that builder wrote them."""
    f = 32 // bits
    lanes = np.zeros(-(-len(codes) // f) * f, dtype=np.uint32)
    lanes[: len(codes)] = codes
    shifts = (np.arange(f, dtype=np.uint32) * np.uint32(bits))[None, :]
    return np.bitwise_or.reduce(lanes.reshape(-1, f) << shifts, axis=1).astype(np.uint32)


class TestPackRoundTrip:
    @pytest.mark.parametrize("bits", [4, 8, 16])
    @pytest.mark.parametrize("n", ROW_COUNTS)
    def test_numpy_round_trip(self, bits, n):
        codes = _codes(bits, n)
        words = packing.pack_codes(codes, bits)
        assert words.dtype == np.uint32
        # whole blocks, the tail block zero-padded
        assert words.shape[0] == packing.packed_words(n, bits) == -(-n // B) * B * bits // 32
        np.testing.assert_array_equal(packing.unpack_codes(words, bits, n), codes)

    @pytest.mark.parametrize("bits", [4, 8, 16])
    def test_layout_is_block_planar(self, bits):
        """Lane l of word j of block b covers row b*B + l*(B/f) + j."""
        f, n = 32 // bits, 2 * B + 77
        codes = _codes(bits, n)
        words = packing.pack_codes(codes, bits)
        run = B // f
        for b, j, lane in [(0, 0, 0), (0, 5, f - 1), (1, run - 1, 1), (2, 76, 0), (2, 77, 0), (2, 3, 1)]:
            row = b * B + lane * run + j
            want = codes[row] if row < n else 0  # the tail block's padding is zero
            assert (words[b * run + j] >> np.uint32(bits * lane)) & np.uint32((1 << bits) - 1) == want

    @pytest.mark.parametrize("bits", [4, 8, 16])
    @pytest.mark.parametrize("n", [999] + ROW_COUNTS[4:])
    def test_jnp_unpack_matches_numpy(self, bits, n):
        import jax.numpy as jnp

        codes = _codes(bits, n)
        words = packing.pack_codes(codes, bits)
        got = np.asarray(packing.unpack_codes_jnp(jnp.asarray(words), bits, n))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, codes.astype(np.int32))
        np.testing.assert_array_equal(got, packing.unpack_codes(words, bits, n, dtype=np.int32))

    @pytest.mark.parametrize("bits", [4, 8, 16])
    def test_jnp_unpack_last_axis_2d(self, bits):
        """Stacked [S, W] layouts pack and unpack along the last axis; a
        shard of whole blocks packs to what the flat codes pack to."""
        import jax.numpy as jnp

        codes = _codes(bits, 3 * 2 * B).reshape(3, 2 * B)
        words = packing.pack_codes(codes, bits)
        assert words.shape == (3, 2 * B * bits // 32)
        np.testing.assert_array_equal(words.reshape(-1), packing.pack_codes(codes.reshape(-1), bits))
        np.testing.assert_array_equal(packing.unpack_codes(words, bits, 2 * B), codes)
        got = np.asarray(packing.unpack_codes_jnp(jnp.asarray(words), bits, 2 * B - 5))
        np.testing.assert_array_equal(got, codes[:, : 2 * B - 5])

    def test_rejects_unsupported_width(self):
        with pytest.raises(ValueError):
            packing.pack_codes(np.zeros(4, np.uint32), 5)
        with pytest.raises(ValueError):
            packing.unpack_codes(np.zeros(1, np.uint32), 3, 4)

    @pytest.mark.parametrize("bits", [4, 8, 16])
    def test_words_of_another_layout_are_refused(self, bits):
        """Interleaved words are not whole blocks: no silent misread."""
        import jax.numpy as jnp

        old = _pack_interleaved(_codes(bits, 1000), bits)
        with pytest.raises(ValueError, match="not whole blocks"):
            packing.unpack_codes(old, bits, 1000)
        with pytest.raises(ValueError, match="not whole blocks"):
            packing.unpack_codes_jnp(jnp.asarray(old), bits, 1000)
        np.testing.assert_array_equal(packing.unpack_interleaved(old, bits, 1000), _codes(bits, 1000))


class TestPallasKeyUnpack:
    @pytest.mark.parametrize("bits", [4, 8, 16])
    @pytest.mark.parametrize("n", [B, 2 * B + 77])
    def test_interpreted_kernel_reads_the_rows_unpack_codes_gives(self, bits, n):
        """The kernel shifts a packed key out of its lane in-register; a
        count and a sum of row numbers per group pin every row's code to
        the one packing.unpack_codes gives (every chunk of a tile, every
        lane, a ragged tail block)."""
        import jax.numpy as jnp

        from pinot_tpu.ops import pallas_scan

        groups = min(1 << bits, 300)
        codes = (_codes(bits, n) % groups).astype(np.uint32)
        words = packing.pack_codes(codes, bits)
        rows = np.arange(n, dtype=np.int32) % 1000
        mask = np.random.default_rng(bits).random(n) < 0.7
        count, total = pallas_scan.fused_group_tables_pallas(
            [("count", jnp.asarray(mask), jnp.asarray(mask), None),
             ("int_sum", jnp.asarray(rows), jnp.asarray(mask), (2, False))],
            jnp.asarray(codes.astype(np.int32)), groups,
            codes_packed=(jnp.asarray(words), bits), interpret=True,
        )
        unpacked = packing.unpack_codes(words, bits, n)
        np.testing.assert_array_equal(
            np.asarray(count), np.bincount(unpacked[mask], minlength=groups))
        np.testing.assert_array_equal(
            np.asarray(total), np.bincount(unpacked[mask], weights=rows[mask], minlength=groups))

    def test_words_short_of_whole_blocks_are_refused(self):
        import jax.numpy as jnp

        from pinot_tpu.ops import pallas_scan

        mask = jnp.ones(1000, bool)
        with pytest.raises(ValueError, match="whole blocks"):
            pallas_scan.fused_group_tables_pallas(
                [("count", mask, mask, None)], jnp.zeros(1000, jnp.int32), 4,
                codes_packed=(jnp.zeros(125, jnp.uint32), 4), interpret=True,
            )


class TestSegmentRoundTrip:
    @pytest.mark.parametrize(
        "card,bits",
        [(3, 4), (16, 4), (17, 8), (256, 8), (257, 16), (40000, 16)],
    )
    def test_build_save_load_parity(self, tmp_path, card, bits):
        n = max(card * 2, 500)
        schema, data = _dict_schema(), _dict_data(n, card, seed=card)
        seg = build_segment(schema, data, "s0", output_dir=str(tmp_path / "s0"))
        c = seg.column("k")
        assert c.code_bits == (bits if bits < 32 else None)
        assert c.packed is not None and c.packed.dtype == np.uint32
        loaded = ImmutableSegment.load(str(tmp_path / "s0"), verify=True)
        lc = loaded.column("k")
        assert lc.code_bits == c.code_bits
        np.testing.assert_array_equal(lc.codes, c.codes)
        np.testing.assert_array_equal(lc.packed, c.packed)
        np.testing.assert_array_equal(lc.decoded(), seg.column("k").decoded())

    def test_wide_dictionary_stays_unpacked(self, tmp_path):
        n, card = 140_000, 70_000  # needs >16 bits -> raw storage
        schema, data = _dict_schema(), _dict_data(n, card, seed=9)
        seg = build_segment(schema, data, "s0", output_dir=str(tmp_path / "s0"))
        c = seg.column("k")
        assert c.code_bits is None and c.packed is None
        loaded = ImmutableSegment.load(str(tmp_path / "s0"), verify=True)
        assert loaded.column("k").code_bits is None
        np.testing.assert_array_equal(loaded.column("k").codes, c.codes)

    def test_nullable_dict_column_round_trip(self, tmp_path):
        schema = _dict_schema(nullable=True)
        data = _dict_data(800, 20, seed=5, null_rate=0.15)
        seg = build_segment(schema, data, "s0", output_dir=str(tmp_path / "s0"))
        c = seg.column("k")
        assert c.code_bits == 8 and c.nulls is not None and c.nulls.sum() > 0
        loaded = ImmutableSegment.load(str(tmp_path / "s0"), verify=True)
        lc = loaded.column("k")
        np.testing.assert_array_equal(lc.nulls, c.nulls)
        np.testing.assert_array_equal(lc.codes, c.codes)
        np.testing.assert_array_equal(lc.packed, c.packed)

    def test_builder_version_stamped(self, tmp_path):
        from pinot_tpu.segment import store

        schema, data = _dict_schema(), _dict_data(200, 10)
        build_segment(schema, data, "s0", output_dir=str(tmp_path / "s0"))
        meta, _ = store.read_segment(str(tmp_path / "s0"))
        assert meta["builderVersion"] == BUILDER_VERSION == 3
        km = meta["columns"][0]
        assert km["codeBits"] == 4 and km[packing.LAYOUT_KEY] == packing.BLOCK_ROWS

    def test_pre_packing_segment_loads_via_raw_path(self, tmp_path):
        """A segment written before packing (no codeBits in column meta)
        must load and decode unchanged through the raw forward index."""
        schema, data = _dict_schema(), _dict_data(300, 10, seed=7)
        seg = build_segment(schema, data, "s0")
        # simulate the v1 builder: strip packing before save -> the .fwd
        # region holds raw codes and col meta carries no codeBits
        seg.columns["k"] = dataclasses.replace(
            seg.columns["k"], code_bits=None, packed=None
        )
        seg.save(str(tmp_path / "s0"))
        from pinot_tpu.segment import store

        meta, _ = store.read_segment(str(tmp_path / "s0"))
        km = meta["columns"][list(seg.columns).index("k")]  # positional meta
        assert "codeBits" not in km
        loaded = ImmutableSegment.load(str(tmp_path / "s0"), verify=True)
        lc = loaded.column("k")
        assert lc.code_bits is None and lc.packed is None
        np.testing.assert_array_equal(lc.decoded(), seg.column("k").decoded())


    @pytest.mark.parametrize("card,bits", [(10, 4), (200, 8), (3000, 16)])
    def test_interleaved_layout_segment_loads_to_the_same_codes(self, tmp_path, monkeypatch, card, bits):
        """A segment file as builder version 2 wrote it (interleaved lane
        words, `codeBits` and no layout stamp) loads to the same codes, and
        to the lane words this build ships: re-packed once, on the host."""
        from pinot_tpu.segment import store

        schema, data = _dict_schema(), _dict_data(max(2 * card, 700), card, seed=card)
        seg = build_segment(schema, data, "s0")
        k = seg.column("k")
        assert k.code_bits == bits
        seg.columns["k"] = dataclasses.replace(k, packed=_pack_interleaved(np.asarray(k.codes), bits))
        write = store.write_segment

        def write_v2(path, meta, regions):
            cols = [{a: v for a, v in cm.items() if a != packing.LAYOUT_KEY} for cm in meta["columns"]]
            write(path, dict(meta, builderVersion=2, columns=cols), regions)

        monkeypatch.setattr(store, "write_segment", write_v2)
        seg.save(str(tmp_path / "s0"))
        monkeypatch.undo()
        meta, regions = store.read_segment(str(tmp_path / "s0"))
        assert meta["builderVersion"] == 2 and packing.LAYOUT_KEY not in meta["columns"][0]
        assert regions["k.fwd"].shape[0] == -(-seg.num_docs // (32 // bits))  # not whole blocks

        loaded = ImmutableSegment.load(str(tmp_path / "s0"), verify=True)
        lc = loaded.column("k")
        assert lc.code_bits == bits
        np.testing.assert_array_equal(lc.codes, k.codes)
        np.testing.assert_array_equal(lc.packed, k.packed)
        np.testing.assert_array_equal(lc.decoded(), k.decoded())
        # saved again it is a segment of this build
        loaded.save(str(tmp_path / "s1"))
        meta, _ = store.read_segment(str(tmp_path / "s1"))
        assert meta["columns"][0][packing.LAYOUT_KEY] == packing.BLOCK_ROWS
        np.testing.assert_array_equal(
            ImmutableSegment.load(str(tmp_path / "s1")).column("k").codes, k.codes)

    def test_unknown_block_size_is_refused(self, tmp_path, monkeypatch):
        from pinot_tpu.segment import store

        schema, data = _dict_schema(), _dict_data(300, 10)
        seg = build_segment(schema, data, "s0")
        write = store.write_segment

        def write_other(path, meta, regions):
            cols = [dict(cm, **({packing.LAYOUT_KEY: 1 << 13} if "codeBits" in cm else {})) for cm in meta["columns"]]
            write(path, dict(meta, columns=cols), regions)

        monkeypatch.setattr(store, "write_segment", write_other)
        seg.save(str(tmp_path / "s0"))
        monkeypatch.undo()
        with pytest.raises(ValueError, match="lane blocks of 8192 rows"):
            ImmutableSegment.load(str(tmp_path / "s0"))


class TestDeviceShipping:
    def test_to_device_packed_opt_in(self):
        import jax

        schema, data = _dict_schema(), _dict_data(400, 10)
        seg = build_segment(schema, data, "s0")
        plain = seg.to_device(columns=["k"])
        assert "codes" in plain["k"] and "codes_packed" not in plain["k"]
        packed = seg.to_device(columns=["k"], packed_codes=True)
        assert "codes_packed" in packed["k"] and "codes" not in packed["k"]
        w = np.asarray(jax.device_get(packed["k"]["codes_packed"]))
        np.testing.assert_array_equal(
            packing.unpack_codes(w, seg.column("k").code_bits, seg.num_docs),
            np.asarray(seg.column("k").codes, dtype=np.uint32),
        )

    def test_plain_and_packed_entries_cached_separately(self):
        schema, data = _dict_schema(), _dict_data(100, 10)
        seg = build_segment(schema, data, "s0")
        a = seg.to_device(columns=["k"])["k"]
        b = seg.to_device(columns=["k"], packed_codes=True)["k"]
        assert a is not b
        assert seg.to_device(columns=["k"])["k"] is a  # cache hit per flavor
        assert seg.to_device(columns=["k"], packed_codes=True)["k"] is b


def _stacked_table(n, card=10, shards=8, seed=1):
    from pinot_tpu.parallel.stacked import StackedTable

    rng = np.random.default_rng(seed)
    schema = Schema("t", [FieldSpec("k", DataType.INT), FieldSpec("v", DataType.LONG, role=FieldRole.METRIC)])
    ids = np.concatenate([np.arange(card), rng.integers(0, card, n - card)]).astype(np.int32)
    rng.shuffle(ids)
    return StackedTable.build(schema, {"k": ids, "v": rng.integers(0, 1000, n)}, shards)


class TestStackedPacking:
    """A stacked table packs a column only where a shard is whole lane
    blocks; every other table ships its codes unpacked."""

    @pytest.fixture(scope="class")
    def st(self):
        return _stacked_table(8 * 2 * B - 300)  # rounded up to 2 blocks a shard

    @pytest.mark.parametrize("card,bits", [(10, 4), (200, 8), (3000, 16)])
    def test_build_packs_per_shard(self, card, bits):
        st = _stacked_table(8 * B - 100, card=card)
        c = st.columns["k"]
        assert c.code_bits == bits
        S, D = c.codes.shape
        assert D == B and st.num_docs == 8 * B - 100
        assert c.packed.shape == (S, D * bits // 32)
        for s in range(S):
            np.testing.assert_array_equal(
                packing.unpack_codes(c.packed[s], bits, D),
                c.codes[s].astype(np.uint32),
            )

    @pytest.mark.parametrize("n,D", [(2000, 256), (8 * (B + 4000), B + 4000), (8 * 31 * 1024, 32 * 1024)])
    def test_shard_rounds_up_to_whole_blocks_only_where_cheap(self, n, D):
        """Padding a shard to whole blocks by more than 1/16 is not worth
        the lanes: such a table keeps its 32-aligned shards and ships its
        codes unpacked (the fallback the engine always had)."""
        import jax

        st = _stacked_table(n)
        c = st.columns["k"]
        assert st.docs_per_shard == D
        assert (c.packed is not None) == (D % B == 0) == (c.code_bits is not None)
        cols, _ = st.to_device(columns=["k"], packed_codes=True, with_valid=False)
        assert ("codes_packed" in cols["k"]) == (D % B == 0)
        if D % B:
            np.testing.assert_array_equal(np.asarray(jax.device_get(cols["k"]["codes"])), c.codes)

    def test_signature_keys_on_code_bits(self):
        st = _stacked_table(8 * B)
        sig_packed = st.signature()
        st.columns["k"] = dataclasses.replace(
            st.columns["k"], code_bits=None, packed=None
        )
        assert st.signature() != sig_packed

    @pytest.mark.parametrize("lo,hi,packed", [(B, 2 * B, True), (0, B, True), (32, 2 * B, False), (0, B + 32, False)])
    def test_to_device_packed_with_doc_slice(self, st, lo, hi, packed):
        """A doc slice ships packed only as whole blocks (as _batching cuts
        a packed table); any other 32-aligned slice ships unpacked codes."""
        import jax

        assert st.docs_per_shard == 2 * B
        cols, _ = st.to_device(
            columns=["k"], doc_slice=(lo, hi), packed_codes=True, with_valid=False
        )
        assert ("codes_packed" in cols["k"]) == packed
        want = st.columns["k"].codes[:, lo:hi]
        if packed:
            w = np.asarray(jax.device_get(cols["k"]["codes_packed"]))
            assert w.shape == (st.num_shards, (hi - lo) * 4 // 32)
            np.testing.assert_array_equal(packing.unpack_codes(w, 4, hi - lo), want.astype(np.uint32))
        else:
            np.testing.assert_array_equal(np.asarray(jax.device_get(cols["k"]["codes"])), want)

    def test_engine_batches_a_packed_table_at_block_bounds(self, st):
        from pinot_tpu.parallel import mesh as mesh_mod
        from pinot_tpu.parallel.engine import DistributedEngine
        from pinot_tpu.sql.parser import parse_query

        eng = DistributedEngine(mesh=mesh_mod.default_mesh(num_devices=8), hbm_cache_bytes=0)
        eng.launch_bytes = 200_000  # two batches a device at this size
        batch_docs, offsets = eng._batching(parse_query("SELECT COUNT(*) FROM t"), st)
        assert batch_docs == B and [o for o, _ in offsets] == [0, B]

    @pytest.mark.parametrize("backend", ["xla", "interpret"])
    @pytest.mark.parametrize("launch_bytes", [None, 200_000])
    def test_engine_answers_over_packed_shards(self, st, monkeypatch, backend, launch_bytes):
        """Filter, single-key and two-key group-bys over packed shards, in
        one launch and cut at block bounds, through the XLA unpack and (a
        single dictionary key) the interpreted kernel's own: against numpy."""
        from pinot_tpu import ops
        from pinot_tpu.parallel.engine import DistributedEngine

        from pinot_tpu.utils.metrics import METRICS

        monkeypatch.setattr(ops, "scan_backend", lambda: backend)
        traced = METRICS.counter(f"scan.traced.{backend}").value
        eng = DistributedEngine(launch_bytes=launch_bytes, hbm_cache_bytes=0)
        eng.register_table("t", st)
        n = st.num_docs
        k = st.columns["k"].dictionary.values[st.columns["k"].codes.reshape(-1)[:n]]
        v = st.columns["v"].values.reshape(-1)[:n].astype(np.int64)
        if launch_bytes:
            from pinot_tpu.sql.parser import parse_query

            assert len(eng._plan(parse_query("SELECT COUNT(*) FROM t"), st).batch_offsets) == 2
        rows = eng.query("SELECT k, COUNT(*), SUM(v) FROM t GROUP BY k ORDER BY k LIMIT 20").rows
        assert [tuple(r) for r in rows] == [
            (int(g), int((k == g).sum()), int(v[k == g].sum())) for g in np.unique(k)]
        assert METRICS.counter(f"scan.traced.{backend}").value > traced
        rows = eng.query("SELECT COUNT(*), SUM(v) FROM t WHERE k BETWEEN 3 AND 6").rows
        sel = (k >= 3) & (k <= 6)
        assert tuple(rows[0]) == (int(sel.sum()), int(v[sel].sum()))


class TestLoweredPlans:
    """The property the layout exists for, on the programs the benchmark
    serves: no array with a minor dimension of a lane factor."""

    @pytest.fixture(scope="class")
    def ssb(self):
        import os
        import sys

        bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
        sys.path.insert(0, bench)
        from lib import plugins, templates

        from pinot_tpu.spi.config import IndexingConfig, TableConfig

        cfg = plugins.load_json("configs", "ssb_flat_sf1")
        schema = Schema(cfg["table"], [FieldSpec(c["name"], DataType[c["type"]], role=FieldRole[c["role"]])
                                       for c in cfg["columns"]])
        tcfg = TableConfig(cfg["table"], indexing=IndexingConfig.from_dict(cfg["table_config"]))
        block = plugins.load_module("datagen", cfg["datagen"]).make_segment(cfg, 7, 0, B + 5000)
        seg = build_segment(schema, {k: v.astype(np.int32) for k, v in block.items()}, "seg0", table_config=tcfg)
        queries = plugins.load_json("queries", cfg["query_set"])["templates"]
        return seg, {name: templates.render(t, t["ssb"]) for name, t in queries.items()}

    @pytest.mark.parametrize("name", ["q1_1", "q2_1"])
    def test_no_narrow_minor_dimension_and_one_unpack_a_column(self, ssb, name):
        import re

        import jax

        from pinot_tpu.query import planner
        from pinot_tpu.sql.parser import parse_query
        from pinot_tpu.utils.metrics import METRICS

        seg, sql = ssb
        plan = planner.plan_segment(parse_query(sql[name]), seg)
        packed = [c for c in plan.needed_columns if seg.column(c).packed is not None]
        assert len(packed) >= 3  # every dictionary column of the flight is packed
        cols = seg.to_device(columns=plan.needed_columns, packed_codes=True)
        assert all("codes_packed" in cols[c] for c in packed)
        params = {k: jax.device_put(v) for k, v in plan.params.items()}

        unpacks = METRICS.counter("scan.traced.lane_unpack")
        before = unpacks.value
        text = plan.fn.lower(cols, params).as_text()
        assert unpacks.value - before == len(packed)  # once a column, at trace time
        shapes = re.findall(r"tensor<((?:\d+x)+)(u?i\d+)>", text)
        assert any(dims.endswith("x128x") for dims, _ in shapes)  # the unpack is there, in whole lanes
        narrow = {f"{dims}{ty}" for dims, ty in shapes if dims.count("x") >= 2 and dims.split("x")[-2] in ("2", "4", "8")}
        assert not narrow, narrow

        jax.block_until_ready(plan.fn(cols, params))
        traced = unpacks.value
        jax.block_until_ready(plan.fn(cols, params))  # warm: the same program, nothing retraced
        assert unpacks.value == traced

    @pytest.mark.parametrize("name", ["q1_1", "q1_2", "q1_3"])
    def test_scalar_sum_holds_no_narrow_row_length_array_under_chunked32(self, ssb, name, monkeypatch):
        """The chip's arithmetic (`chunked32`; the case above lowers the CPU's
        `wide`): Q1's exact sum reduces its limbs whole, so no tensor of ANY
        element type pairs a row-length dimension with a minor one below a
        lane row (PR 38 removed an f32[rows, 5] limb stack, its pad and its
        [chunks, 65536, 5] view), and `scan.traced.scalar_limbs` says so at
        trace time, once a summed column."""
        import re

        import jax

        from pinot_tpu import ops
        from pinot_tpu.ops import segmented
        from pinot_tpu.query import planner
        from pinot_tpu.sql.parser import parse_query
        from pinot_tpu.utils.metrics import METRICS

        monkeypatch.setattr(ops, "accum_policy", lambda: "chunked32")
        monkeypatch.setattr(segmented, "accum_policy", lambda: "chunked32")
        planner.plan_cache_clear()  # the cache does not key on the policy
        seg, sql = ssb
        try:
            plan = planner.plan_segment(parse_query(sql[name]), seg)
            assert plan.kind == "aggregation"
            cols = seg.to_device(columns=plan.needed_columns, packed_codes=True)
            params = {k: jax.device_put(v) for k, v in plan.params.items()}
            limbs = METRICS.counter("scan.traced.scalar_limbs")
            before = limbs.value
            text = plan.fn.lower(cols, params).as_text()
            assert limbs.value - before == 1  # SUM(lo_extendedprice * lo_discount): one column
            # but for a dictionary look-up's codes as [rows, 1]: StableHLO's form of a gather's
            # index vector, a bitcast to the compiler (lo_discount's values by code)
            index = set(re.findall(r'"stablehlo\.gather"\(%\w+, (%\w+)\)', text))
            body = "\n".join(line for line in text.splitlines() if '"stablehlo.gather"' not in line
                             and line.split(" = ")[0].strip() not in index)
            shapes = re.findall(r"tensor<((?:\d+x)+)(\w+)>", body)
            assert any(dims.endswith("x128x") for dims, _ in shapes)  # the lane unpack, in whole lanes
            narrow = set()
            for dims, ty in shapes:
                sizes = [int(d) for d in dims.split("x") if d]
                if len(sizes) >= 2 and sizes[-1] < 128 and max(sizes[:-1]) >= seg.num_docs:
                    narrow.add(f"{dims}{ty}")
            assert not narrow, narrow

            got = jax.block_until_ready(plan.fn(cols, params))
            traced = limbs.value
            again = jax.block_until_ready(plan.fn(cols, params))  # warm: the same program, nothing retraced
            assert limbs.value == traced
            assert jax.tree_util.tree_all(jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), got, again))
        finally:
            planner.plan_cache_clear()
